"""The pump's drain in a traced run: the profiler's stop runs at the close,
while requests are in flight, and takes tens of seconds on the chip (PR 26:
25.6-34.9 s). That is the harness's own time: the drain is counted from the
stop's return, so a stop that outlasts `_DRAIN_S` leaves no request
unfinished (PR 27 was refused `outputs_incorrect` on such a run)."""
import time
import types

import find

serve = find.load("drivers", "serve")


class _Engine:
    """As much of `InferenceEngine` as the pump calls: every turn gives
    each request in flight one token and takes 10 ms."""

    slots = 4

    def __init__(self):
        self.live, self.want = {}, {}

    def submit(self, req):
        self.live[req.rid] = []
        self.want[req.rid] = req.max_new_tokens

    def queue_depth(self):
        return 0

    def inflight(self):
        return len(self.live)

    def progress(self):
        return {rid: list(t) for rid, t in self.live.items()}

    def cancel(self, rid):
        self.live.pop(rid, None)

    def turn(self, results):
        time.sleep(0.01)
        for rid in list(self.live):
            self.live[rid].append(7)
            if len(self.live[rid]) == self.want[rid]:
                results[rid] = types.SimpleNamespace(
                    tokens=self.live.pop(rid), ttft_ms=1.0, prefill_ms=0.5)


class _SlowStop:
    """A tracer whose `finish` outlasts the drain."""

    def __init__(self, stop_s):
        self.stop_s, self.stopped_at = stop_s, None

    def poll(self, now):
        pass

    def finish(self, now):
        time.sleep(self.stop_s)
        self.stopped_at = now


def _run(monkeypatch, tracer, max_new):
    monkeypatch.setattr(serve, "_DRAIN_S", 0.3)
    # due at 0.05 s, a token every 10 ms while the pump turns the engine:
    # some 15 by the close (0.2 s), the rest in the drain
    sched = [{"due": 0.05, "prompt": [1, 2, 3], "max_new": max_new}]
    p = serve.pump(_Engine(), sched, 0.2, withdraw_at_close=False,
                   tracer=tracer)
    return p, serve.summarize(p, {"vocab": 50}, kv_bytes_per_token=8)


def test_the_profilers_stop_does_not_count_towards_the_drain(monkeypatch):
    tracer = _SlowStop(stop_s=0.5)          # outlasts the 0.3 s drain
    p, summ = _run(monkeypatch, tracer, 30)  # 0.15 s of work is left
    assert tracer.stopped_at == p["closed"]  # stopped at the close
    assert summ["unfinished"] == 0 and summ["wrong"] == 0
    assert len(p["rec"][0]["tokens"]) == 30
    # the drain is what followed the stop's return, and is logged so
    assert p["end"] - p["closed"] > 0.5 > 0.3 > p["drain_s"] > 0


def test_an_untraced_run_drains_from_the_close(monkeypatch):
    p, summ = _run(monkeypatch, None, 80)    # 0.65 s of work is left
    assert summ["unfinished"] == 1           # 0.3 s of drain is not enough
    assert p["drain_s"] == p["end"] - p["closed"]
    assert 0.3 < p["drain_s"] < 0.4
