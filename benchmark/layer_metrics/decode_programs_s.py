"""Seconds the engine spent on its decode programs before traffic: lowering,
compiling (or fetching from the cache) and reading the compiled form's cache
traffic, summed over the ``decode_programs`` of the engine's summary."""


def read(run):
    progs = [p for p in run.get("engine", {}).get("decode_programs", [])
             if "compile_s" in p]
    if not progs:
        return None
    return sum(p["lower_s"] + p["compile_s"] + p["traffic_s"] for p in progs)
