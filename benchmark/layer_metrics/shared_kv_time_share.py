"""Share of the device's busy time under the decode program's layers that
read the one shared buffer of keys and values (``reduced["by_scope"]``:
``jit_rt_decode/attn_full``, which also writes it, and ``/attn_cross``, which
only reads: ``ray_tpu/models/sambay.py``). A program without such layers has
neither scope."""


def read(run):
    t = run.get("trace")
    if not t or not t.get("busy_s"):
        return None
    own = sum(t.get("by_scope", {}).get("jit_rt_decode/" + scope, 0.0)
              for scope in ("attn_full", "attn_cross"))
    return 100.0 * own / t["busy_s"] if own else None
