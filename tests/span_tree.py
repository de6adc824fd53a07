"""The tracer's ``B``/``E`` events as spans, for the tests of the live span
trees (``test_serving_spans.py``, ``test_fedllm_spans.py``)."""


def spans_of(events):
    """B/E pairs per thread as dicts: name, tid, t0, t1 (µs), args (both
    events' merged), id, parent."""
    stacks, out = {}, []
    for ev in events:
        if ev["ph"] == "B":
            stacks.setdefault(ev["tid"], []).append(ev)
        elif ev["ph"] == "E":
            stack = stacks[ev["tid"]]
            # a live thread's spans never cross; a synthetic lane's pairs
            # (written afterwards, equal end times) pair by name
            assert ev["tid"] < 0 or stack[-1]["name"] == ev["name"], ev
            b = stack.pop(max(i for i, o in enumerate(stack)
                              if o["name"] == ev["name"]))
            args = {**b.get("args", {}), **ev.get("args", {})}
            out.append({"name": ev["name"], "tid": ev["tid"],
                        "t0": b["ts"], "t1": ev["ts"], "args": args,
                        "id": args["span_id"], "parent": args.get("parent")})
    return out


def named(spans, name):
    return [s for s in spans if s["name"] == name]


def children(spans, parent):
    """The spans opened under ``parent``, in time order."""
    return sorted((s for s in spans if s["parent"] == parent["id"]),
                  key=lambda s: s["t0"])
