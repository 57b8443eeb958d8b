"""Seconds the worker that holds the chip spent in its first ``jax.devices()``."""


def read(run):
    return run["device"].get("backend_init_s")
