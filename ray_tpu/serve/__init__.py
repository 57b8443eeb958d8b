"""ray_tpu.serve — online model serving on the TPU-native runtime.

Reference analog: ``python/ray/serve`` (62.8k LoC): the controller/proxy/
replica triad, power-of-two routing, dynamic batching and ongoing-requests
autoscaling, rebuilt TPU-first: replicas pin whole chips via
``ray_actor_options={"num_tpus": N}``, and ``@serve.batch`` exists to keep
the MXU fed with large fused batches.

    from ray_tpu import serve

    @serve.deployment(num_replicas=2)
    class Model:
        @serve.batch(max_batch_size=8)
        async def predict(self, xs): return model(stack(xs))
        async def __call__(self, request): return await self.predict(request.json())

    handle = serve.run(Model.bind())
    handle.remote(...).result()
"""

from ray_tpu.serve.api import (Application, Deployment, delete, deployment,
                               get_app_handle, get_deployment_handle,
                               http_port, run, shutdown, start, start_grpc,
                               status)
from ray_tpu.serve.api import _forget_controller as _forget_controller_for_tests
from ray_tpu.serve.asgi import (ASGIResponse, ASGIResponseStart, asgi_app,
                                ingress)
from ray_tpu.serve.batching import batch
from ray_tpu.serve.config import (AutoscalingConfig, DeploymentConfig,
                                  HTTPOptions)
from ray_tpu.serve.handle import (DeploymentHandle, DeploymentResponse,
                                  DeploymentResponseGenerator)
from ray_tpu.serve.multiplex import get_multiplexed_model_id, multiplexed
from ray_tpu.serve.grpc_proxy import grpc_request
from ray_tpu.serve.obs import get_serve_request_id
from ray_tpu.serve.api import detailed_status, proxy_ports
from ray_tpu.serve.proxy import ServeRequest
from ray_tpu.serve.llm import continuous_llm_app

__all__ = [
    "ASGIResponse", "ASGIResponseStart",
    "Application", "AutoscalingConfig", "Deployment", "DeploymentConfig",
    "DeploymentHandle", "DeploymentResponse", "DeploymentResponseGenerator",
    "HTTPOptions", "ServeRequest",
    "asgi_app", "batch", "continuous_llm_app", "delete", "deployment",
    "detailed_status",
    "get_app_handle",
    "ingress",
    "get_deployment_handle", "get_multiplexed_model_id", "grpc_request",
    "get_serve_request_id",
    "http_port", "multiplexed", "proxy_ports", "run",
    "shutdown", "start", "start_grpc",
    "status",
]
