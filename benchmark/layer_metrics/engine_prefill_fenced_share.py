"""Share of the engine's tick wall spent in prefill proper: staging the
prompt, the compiled call and the host read of its first token (the
recorder's ``prefill`` span, fenced). With ``engine_admission_share`` and the
``kv_restore`` share it sums to ``engine_prefill_share``. Not read from a
recorder whose ``prefill`` ends at dispatch (one without ``decode_parts_s``)."""


def read(run):
    e = run.get("engine", {})
    if not e.get("tick_wall_s") or "decode_parts_s" not in e:
        return None
    return 100.0 * e.get("phase_s", {}).get("prefill", 0.0) / e["tick_wall_s"]
