"""entry layer (``serving/server.py``): host time a scene spends outside
its forward — scene latency (submit to result, host clock) minus the
synced duration of the forward that served it (the program's ``forward``
span event, ``dur_s``).  Mean over the window's scenes, in ms."""


def read(m):
    fwd = {e["request"]: e["dur_s"] for e in m.events
           if e["phase"] == "forward" and "request" in e}
    vals = [(s["latency_s"] - fwd[s["request"]]) * 1e3 for s in m.scenes if s["request"] in fwd]
    return sum(vals) / len(vals) if vals else None
