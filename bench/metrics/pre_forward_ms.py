"""scheduler layer (``serving/vggt_engine.py``): host time before each
forward — the program's batch-level ``vggt.assemble`` span event (tier
params, bucket, padding and concatenation, up to the forward's
dispatch), mean ``dur_s`` over the forwards (``forward`` ordinal) whose
``requests`` include a counted scene, in ms."""

PHASES = ("vggt.assemble",)


def read(m):
    counted = {s["request"] for s in m.scenes}
    per = {}
    for e in m.events:
        if e["phase"] in PHASES and counted.intersection(e.get("requests", ())):
            per[e["forward"]] = per.get(e["forward"], 0.0) + e["dur_s"]
    return 1e3 * sum(per.values()) / len(per) if per else None
