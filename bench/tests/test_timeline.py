"""Token times, TTFT and inter-token gaps rebuilt from hand-made spans."""
import numpy as np
import pytest

from harness import timeline


def _x(name, t, dur, **args):
    return dict(name=name, ph="X", ts=t * 1e6, dur=dur * 1e6, args=args)


def _i(name, t, **args):
    return dict(name=name, ph="i", ts=t * 1e6, args=args)


def test_prefill_stall_lengthens_gaps():
    """Two requests; request 2 is admitted between steps 1 and 2 with a
    slow prefill, which stalls request 1's next token."""
    ev = [
        _x("admit_dispatch", 0.01, 0.01, rid=1),
        _i("splice", 0.05, rid=1),
        _x("token_step", 0.10, 0.001, step=0, active=1),
        _x("token_step", 0.20, 0.001, step=1, active=1),
        _x("admit_dispatch", 0.26, 0.01, rid=2),          # the stall
        _i("splice", 0.60, rid=2),
        _x("token_step", 0.70, 0.001, step=2, active=2),
        _i("retire", 0.79, rid=1),
        _x("token_step", 0.80, 0.001, step=3, active=1),
    ]
    step_done = {1: 0.15, 2: 0.25, 3: 0.75, 4: 0.85}
    steps, splice, dispatch, retire = timeline.parse(ev, 0.0, step_done)
    times = timeline.token_times(steps, splice, retire)
    assert times[1] == pytest.approx([0.05, 0.15, 0.25, 0.75])
    assert times[2] == pytest.approx([0.60, 0.75, 0.85])
    st = timeline.window_stats(times, {1: 0.001, 2: 0.25}, dispatch, 0.0, 1.0)
    assert st["tokens"] == 7
    assert sorted(st["itl"]) == pytest.approx([0.10, 0.10, 0.10, 0.15, 0.50])
    assert sorted(st["ttft"]) == pytest.approx([0.049, 0.35])
    assert sorted(st["queue_wait"]) == pytest.approx([0.009, 0.01])


def test_window_edges_and_waiting_requests():
    ev = [_i("splice", 0.5, rid=1),
          _x("token_step", 0.6, 0.001, step=0, active=1),
          _x("token_step", 0.7, 0.001, step=1, active=1)]
    steps, splice, dispatch, retire = timeline.parse(ev, 0.0, {1: 0.65, 2: 0.75})
    times = timeline.token_times(steps, splice, retire)
    st = timeline.window_stats(times, {1: 0.4, 2: 0.62}, dispatch, 0.55, 0.72)
    assert st["tokens"] == 1                    # only step 0's token is inside
    assert st["itl"] == pytest.approx([0.15])
    assert st["ttft"] == pytest.approx([0.10])  # rid 2 waits until the close
    assert st["due"] == 1
    assert timeline.percentile(np.asarray([]), 90) is None
