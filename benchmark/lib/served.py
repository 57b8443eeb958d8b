"""The replica the serve cells run: ``serve.llm.ContinuousLLM`` itself, given
a published configuration instead of a preset's name, with the few methods
the benchmark needs beside the chip. The configuration's family
(``benchmark/families/``) gives the program's config, the function that makes
the weights and the reference; nothing here knows an architecture. Requests
go the normal way: HTTP proxy -> handle -> replica -> ``ContinuousEngine`` ->
stream back.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Dict, Iterator, List, Optional

from ray_tpu.serve.llm import ContinuousLLM

from benchmark.lib import spec


@contextlib.contextmanager
def _front_door(name: str, cfg: Any, init_params: Callable[[Any, Any], Any],
                facts: Dict[str, float]) -> Iterator[None]:
    """``ContinuousLLM.__init__`` takes a preset's *name*, looks it up in
    ``llama.PRESETS`` and calls ``llama.init_params(key, cfg)``: the only way
    in for a config object and for weights of another module's making is
    through those two names, for the length of the call. The weights are
    the benchmark's to make: on the device, in one jitted call from the
    seed, in the type they are served in. (A program change takes this
    function's place: PERF.md, Open questions.)"""
    import jax

    from ray_tpu.models import llama

    def jitted(rng, cfg):
        llama.init_params = eager  # the family's function may build on it
        t = time.perf_counter()
        params = jax.block_until_ready(
            jax.jit(init_params, static_argnums=1)(rng, cfg))
        facts["weights_s"] = time.perf_counter() - t
        return params

    eager, llama.PRESETS[name], llama.init_params = llama.init_params, cfg, jitted
    try:
        yield
    finally:
        llama.init_params = eager


class BenchLLM(ContinuousLLM):
    def __init__(self, cfg_file: Dict[str, Any], n_layers: int, *,
                 trace_dir: str, **engine_args: Any):
        from ray_tpu.util.compile_cache import CompileCounter

        self._compiles = CompileCounter()
        t0 = time.perf_counter()
        import jax

        jax.devices()
        self._facts = {"backend_init_s": time.perf_counter() - t0}
        self._cfg_file = cfg_file
        self._family = spec.load_family(cfg_file["family"])
        self._trace_dir = trace_dir
        name = cfg_file["name"]
        cfg = self._family.program_config(cfg_file, n_layers,
                                          max_seq_len=engine_args["max_len"])
        with _front_door(name, cfg, self._family.init_params, self._facts):
            t = time.perf_counter()
            super().__init__(name, name=name, **engine_args)
        self._facts["engine_init_s"] = (time.perf_counter() - t
                                        - self._facts["weights_s"])

    # ---- what the parent asks for through the handle --------------------------

    def mark(self) -> Dict[str, Any]:
        """The replica's clock and compile counter, for the window's ends."""
        return {"t": time.time(), **self._compiles.snapshot()}

    def device_facts(self) -> Dict[str, Any]:
        from benchmark.lib import model

        return {**model.device_facts(), **self._facts,
                **self._compiles.snapshot()}

    def recorder_window(self, t0: float, t1: float) -> Dict[str, Any]:
        """The engine recorder's host-clock spans inside [t0, t1)."""
        rec = self.engine._recorder
        out = rec.window_summary(t0, t1)
        out["ticks"] = [
            {"k": t["k"], "bucket": t["bucket"], "active": t["active"],
             "decode_step_s": t["phases"].get("decode_step", 0.0)}
            for t in rec.ticks(0) if t0 <= t["t"] < t1]
        return out

    def start_trace(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the device and TraceMe spans only
        jax.profiler.start_trace(self._trace_dir, profiler_options=options)

    def stop_trace(self) -> Optional[Dict[str, Any]]:
        import jax

        from benchmark.lib import trace

        jax.profiler.stop_trace()
        path = trace.newest_xplane(self._trace_dir)
        # the engine's tick loop is program code and sets no annotation
        return path and trace.reduce_trace(
            path, idle_label="engine-tick-unattributed")

    def reference_check(self, samples: List[Dict[str, List[int]]]
                        ) -> List[Dict[str, Any]]:
        """For each sampled request, how far every streamed token's logit
        lies under the best one of the float32 reference forward over the
        prompt and the answer so far. Contexts are padded to ``max_len`` so
        that one program serves every sample (causal: the padding is never
        seen by what comes before it)."""
        import jax.numpy as jnp
        import numpy as np

        out, size = [], self.engine.max_len
        for s in samples:
            prompt, toks = s["prompt"], s["tokens"]
            first, n = len(prompt) - 1, len(toks)
            ctx, following = np.zeros((1, size), np.int32), np.zeros(size, np.int32)
            ctx[0, :first + n] = prompt + toks[:-1]
            following[first:first + n] = toks
            m = {k: np.asarray(v)[first:first + n] for k, v in
                 self._family.token_margins(
                     self.params, jnp.asarray(ctx), jnp.asarray(following),
                     self._cfg_file, rows=(first, first + n)).items()}
            out.append({"tokens": n, "finite": bool(m["finite"].all()),
                        "worst_margin": float(m["margin"].max()),
                        "not_argmax": int((m["margin"] > 0).sum()),
                        "logit_scale": float(m["scale"].max())})
        return out
