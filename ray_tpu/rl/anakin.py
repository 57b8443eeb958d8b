"""Anakin-fused rollouts: env + policy + learner in ONE compiled launch.

The Podracer architecture (arxiv 2104.06272) applied to this RL stack:
instead of the host loop in ``env_runner.py`` (numpy env steps
interleaved with per-step jitted inference — one dispatch per env step),
the whole iteration compiles into a single XLA program:

    rollout (``lax.scan`` over T steps, ``vmap`` over B envs)
      → GAE advantages (reverse ``lax.scan``)
        → PPO update (``lax.scan`` over epochs)

Zero host↔device transfers inside the iteration; the host only sees the
final metrics pytree. On a TPU mesh the same program shards over chips
(the batch axis is embarrassingly parallel).

The fused step is compiled EXACTLY ONCE per (config, shapes):
``AnakinRunner.compile_count()`` exposes the jit cache size so tests can
assert the single-launch property instead of trusting the docstring.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_tpu.rl import models
from ray_tpu.rl.algorithms.ppo import make_ppo_loss
from ray_tpu.rl.jax_env import make_jax_env


@dataclasses.dataclass
class AnakinConfig:
    """One fused-iteration recipe (PPO on a pure-JAX env)."""

    env: str = "CartPole-v1"
    num_envs: int = 64
    rollout_len: int = 32
    hidden: Tuple[int, ...] = (64, 64)
    lr: float = 3e-4
    gamma: float = 0.99
    lam: float = 0.95
    clip_param: float = 0.2
    vf_coeff: float = 0.5
    entropy_coeff: float = 0.01
    num_epochs: int = 2
    grad_clip: float = 0.5
    seed: int = 0

    @property
    def env_steps_per_iter(self) -> int:
        return self.num_envs * self.rollout_len


def make_anakin_step(cfg: AnakinConfig, env_cls=None):
    """Build the fused iteration: ``step(carry) -> (carry, metrics)``.

    ``carry`` = (params, opt_state, env_state, obs, key). The function is
    pure and jit-ready; :class:`AnakinRunner` owns the single ``jax.jit``
    wrapping so the compile count is observable.
    """
    env_cls = env_cls or make_jax_env(cfg.env)
    spec = env_cls.spec
    loss_fn = make_ppo_loss(spec, cfg.clip_param, cfg.vf_coeff,
                            cfg.entropy_coeff)
    opt = optax.chain(optax.clip_by_global_norm(cfg.grad_clip),
                      optax.adam(cfg.lr))
    T = cfg.rollout_len

    def step(carry):
        params, opt_state, env_state, obs, key = carry

        def rollout_body(c, _):
            env_state, obs, key = c
            key, sub = jax.random.split(key)
            logits = models.policy_logits(params, obs)
            vals = models.value(params, obs)
            actions = models.categorical_sample(sub, logits)
            logp = models.categorical_logp(logits, actions)
            env_state, next_obs, rew, done = env_cls.step_batch(
                env_state, actions)
            return ((env_state, next_obs, key),
                    (obs, actions, logp, vals, rew, done))

        (env_state, obs, key), traj = jax.lax.scan(
            rollout_body, (env_state, obs, key), None, length=T)
        obs_t, act_t, logp_t, val_t, rew_t, done_t = traj
        last_val = models.value(params, obs)

        def gae_body(c, inp):
            last_gae, next_val = c
            rew, val, done = inp
            nonterminal = 1.0 - done.astype(jnp.float32)
            delta = rew + cfg.gamma * next_val * nonterminal - val
            last_gae = delta + cfg.gamma * cfg.lam * nonterminal * last_gae
            return (last_gae, val), last_gae

        (_, _), adv_t = jax.lax.scan(
            gae_body, (jnp.zeros_like(last_val), last_val),
            (rew_t, val_t, done_t), reverse=True)
        ret_t = adv_t + val_t

        flat = lambda a: a.reshape((T * cfg.num_envs,) + a.shape[2:])  # noqa: E731
        batch = {"obs": flat(obs_t), "actions": flat(act_t),
                 "logp": flat(logp_t), "advantages": flat(adv_t),
                 "value_targets": flat(ret_t)}

        def update_body(c, _):
            params, opt_state = c
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch, None)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state), (loss, aux["entropy"], aux["kl"])

        (params, opt_state), (losses, entropies, kls) = jax.lax.scan(
            update_body, (params, opt_state), None, length=cfg.num_epochs)

        metrics = {
            "reward_mean_per_step": jnp.mean(rew_t),
            "episodes_done": jnp.sum(done_t),
            "loss": losses[-1],
            "entropy": entropies[-1],
            "kl": kls[-1],
            "value_mean": jnp.mean(val_t),
        }
        return (params, opt_state, env_state, obs, key), metrics

    return step


class AnakinRunner:
    """Owns the fused step's single jit + the training carry.

    The entire iteration — rollout, advantage, update — is ONE launch;
    host code only converts the returned metrics. ``compile_count()``
    reports how many programs the jit cache holds (the fusion test
    asserts it stays at 1 across iterations).
    """

    def __init__(self, cfg: Optional[AnakinConfig] = None, **overrides):
        self.cfg = cfg or AnakinConfig(**overrides)
        env_cls = make_jax_env(self.cfg.env)
        self._env_cls = env_cls
        key = jax.random.key(self.cfg.seed)
        k_params, k_env, k_run = jax.random.split(key, 3)
        params = jax.tree_util.tree_map(
            jnp.asarray,
            models.init_policy(k_params, env_cls.spec,
                               hidden=self.cfg.hidden))
        opt = optax.chain(optax.clip_by_global_norm(self.cfg.grad_clip),
                          optax.adam(self.cfg.lr))
        opt_state = opt.init(params)
        env_state, obs = env_cls.reset_batch(k_env, self.cfg.num_envs)
        self._carry = (params, opt_state, env_state, obs, k_run)
        self._step_fn = jax.jit(make_anakin_step(self.cfg, env_cls))
        self.iterations = 0
        self.env_steps_total = 0

    @property
    def params(self):
        return self._carry[0]

    def compile_count(self) -> int:
        """Programs in the fused step's jit cache (1 == fully fused)."""
        return int(self._step_fn._cache_size())

    def train(self, iterations: int = 1) -> Dict[str, Any]:
        """Run N fused iterations; returns the LAST iteration's metrics
        (converted host-side, outside the compiled program)."""
        metrics = None
        for _ in range(iterations):
            self._carry, metrics = self._step_fn(self._carry)
        self.iterations += iterations
        self.env_steps_total += iterations * self.cfg.env_steps_per_iter
        out = {k: float(np.asarray(v)) for k, v in metrics.items()}
        out["env_steps_total"] = self.env_steps_total
        out["iterations"] = self.iterations
        return out
