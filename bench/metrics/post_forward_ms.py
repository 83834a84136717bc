"""scheduler layer (``serving/vggt_engine.py``): host time after each
forward — the program's batch-level ``vggt.check`` (finiteness
reductions and their host read) and ``vggt.deliver`` (slicing and
delivery) span events, mean summed ``dur_s`` per forward (``forward``
ordinal) over the forwards whose ``requests`` include a counted scene,
in ms."""

PHASES = ("vggt.check", "vggt.deliver")


def read(m):
    counted = {s["request"] for s in m.scenes}
    per = {}
    for e in m.events:
        if e["phase"] in PHASES and counted.intersection(e.get("requests", ())):
            per[e["forward"]] = per.get(e["forward"], 0.0) + e["dur_s"]
    return 1e3 * sum(per.values()) / len(per) if per else None
