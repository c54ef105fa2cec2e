import math
from types import SimpleNamespace

import numpy as np
import pytest

from bench import stats
from bench.loop import RunLog, Sent


def _sent(due, t_first=-1.0, n_tokens=0, t_last=-1.0, history=None):
    h = SimpleNamespace(t_first=t_first, tokens=list(range(n_tokens)),
                        history=history or [(due, "QUEUED")],
                        state="DONE" if n_tokens else "QUEUED")
    return Sent(req=None, due=due, submitted=due + 0.01, handle=h,
                t_last=t_last)


def _log(sent, **kw):
    base = dict(t_start=0.0, due0=10.0, due1=20.0, t0=10.05, t1=20.1,
                t_cap=50.0, t_end=50.0, tokens_t0=100, tokens_t1=700)
    base.update(kw)
    return RunLog(sent=sent, **base)


def test_percentile_is_numpy_linear():
    xs = [0.1, 0.4, 0.2, 0.9, 0.3]
    assert stats.percentile(xs, 95) == pytest.approx(np.percentile(xs, 95))
    assert stats.percentile([], 95) is None
    assert stats.percentile([math.nan, 1.0], 50) == 1.0


def test_latency_counts_from_the_due_time():
    # submitted 0.01 s late, first token 0.5 s after it was due
    s = _sent(12.0, t_first=12.5, n_tokens=5, t_last=13.3)
    assert stats.ttft(s, 50.0) == pytest.approx(0.5)
    assert stats.tpot(s) == pytest.approx(0.8 / 4)
    assert stats.lateness(_log([s])) == [pytest.approx(0.01)]


def test_window_holds_requests_due_in_it():
    a, b, c = _sent(9.99, 10.5, 2, 11.0), _sent(10.0, 10.6, 2, 11.0), \
        _sent(20.0, 20.5, 2, 21.0)
    assert _log([a, b, c]).in_window() == [b]


def test_request_without_first_token_at_cap_fails():
    s = _sent(15.0)
    log = _log([s])
    assert stats.failed(log) == [s]
    # counted in the tail at the cap, not dropped from it
    assert stats.ttft(s, log.t_cap) == pytest.approx(35.0)
    assert stats.ttfts(log) == [pytest.approx(35.0)]
    assert math.isnan(stats.tpot(s))


def test_request_cut_at_cap_counts_with_its_tokens():
    # 3 tokens by the cap: its gaps count, it has not failed
    s = _sent(15.0, t_first=16.0, n_tokens=3, t_last=17.0)
    log = _log([s])
    assert stats.failed(log) == []
    assert stats.tpot(s) == pytest.approx(0.5)


def test_queue_wait_reads_the_state_history():
    s = _sent(12.0, history=[(12.02, "QUEUED"), (12.7, "PREFILLING"),
                             (12.9, "TRANSFERRING")])
    assert stats.queue_wait(s, 50.0) == pytest.approx(0.7)


def test_tokens_per_s_over_the_window_marks():
    log = _log([])
    assert stats.tokens_per_s(log) == pytest.approx(600 / (20.1 - 10.05))
