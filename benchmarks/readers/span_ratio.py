"""The ratio of two arguments of the program's spans, each summed over the
spans of one name that end inside the run's window and carry both (a share of
totals: the mean of the spans' own ratios would weigh an empty tick like a
full one).  A program whose spans carry no such arguments (an older one) gives
``None``."""

from __future__ import annotations

from typing import Optional

from readers import spans


def read(args: dict, run) -> Optional[float]:
    """``span``: the spans' name; ``over`` / ``under``: the arguments whose
    sums are divided."""
    from fedml_tpu.obs import get_tracer
    tracer = get_tracer()
    origin = getattr(tracer, "origin_s", None)
    if origin is None or run.window is None:
        return None
    rows = [s["args"] for s in spans.in_window(spans.paired(tracer.events(), origin), run.window)
            if s["name"] == args["span"] and args["over"] in s["args"] and args["under"] in s["args"]]
    under = sum(r[args["under"]] for r in rows)
    return sum(r[args["over"]] for r in rows) / under if under else None
