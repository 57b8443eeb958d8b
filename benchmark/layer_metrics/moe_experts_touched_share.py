"""Share of the expert slots (experts x layers x steps of the window's
decode launches) that received at least one row: what part of the experts'
weights a step had to read. ``decode_hbm_share`` counts all of them."""


def read(run):
    moe = run.get("engine", {}).get("moe_decode") or {}
    slots = moe.get("moe_expert_slots")
    return 100.0 * moe["moe_experts_touched"] / slots if slots else None
