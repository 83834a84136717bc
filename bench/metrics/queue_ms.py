"""scheduler layer (``serving/vggt_engine.py``): mean time from a scene's
``enqueue`` to its ``admit`` into a forward, from the program's span
events (``obs/trace.py``, monotonic ``t``), in ms."""


def read(m):
    first = {}
    for e in m.events:
        if e["phase"] in ("enqueue", "admit") and "request" in e:
            first.setdefault((e["phase"], e["request"]), e["t"])
    vals = [(first[("admit", s["request"])] - first[("enqueue", s["request"])]) * 1e3
            for s in m.scenes
            if ("admit", s["request"]) in first and ("enqueue", s["request"]) in first]
    return sum(vals) / len(vals) if vals else None
