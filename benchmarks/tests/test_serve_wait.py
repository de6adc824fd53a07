"""``drivers/serve.py::finish`` waits ``WAIT_S`` for the answers still open
from its own start, not from the window's close: in a traced run
``stop_trace`` lies between the two and took 45-71 s of the minute (PERF.md
§7, PR 32), after which a deadline counted from the close had passed before a
socket was read."""

import time
import types

from conftest import BENCH  # noqa: F401  (puts the benchmark on the path)

from drivers import serve


class StubClient:
    def __init__(self):
        self.deadlines = []

    def drain(self, deadline: float) -> None:
        self.deadlines.append((time.perf_counter(), deadline))


class StubEngine:
    def kv_stats(self):
        return {"ticks": 0, "prefill_chunks": 0, "pages_free": 10, "pool_pages": 11}


def test_the_wait_is_counted_from_finish_not_from_the_close():
    client = StubClient()
    seconds = 8.0
    # the window closed 70 s ago: a stop_trace as long as the longest PR 32 met
    t0 = time.perf_counter() - seconds - 70.0
    state = {"client": client, "srv": types.SimpleNamespace(_engine=StubEngine()),
             "records": [], "t0": t0, "ticks0": StubEngine().kv_stats(),
             "ticks1": StubEngine().kv_stats()}
    run = types.SimpleNamespace(seconds=seconds, counters={},
                                cell={"engine": {"page_tokens": 16}})
    started = time.perf_counter()
    result = serve.finish(state, run)
    (called, deadline), = client.deadlines
    assert started + serve.WAIT_S <= deadline <= called + serve.WAIT_S
    # ``drain_s`` still counts from the close: it holds the stop_trace
    assert result["notes"]["drain_s"] >= 70.0
    assert result["window"] == (t0, t0 + seconds)
