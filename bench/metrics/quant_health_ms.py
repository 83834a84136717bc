"""model step (``obs/quant_health.py``): host time spent inside the
quant-health callbacks during a forward — the ``quant_health_s`` label of
the program's per-request ``forward`` events (the process-wide callback
time read before and after the forward), mean over the forwards
(``forward`` ordinal) that served a counted scene, in ms."""


def read(m):
    counted = {s["request"] for s in m.scenes}
    per = {e["forward"]: e["quant_health_s"] for e in m.events
           if e["phase"] == "forward" and e.get("request") in counted
           and "forward" in e and "quant_health_s" in e}
    return 1e3 * sum(per.values()) / len(per) if per else None
