"""The span recorder in runtime/monitor.py: parents from nesting,
attributes kept, a bounded ring, and one stack per thread."""

import threading

from repro.runtime import monitor
from repro.runtime.monitor import SPAN_RING, recent_spans, span


def _since(first_id):
    return {sp[3]: sp for sp in recent_spans() if sp[3] > first_id}


def _last_id():
    """An id above every span recorded so far."""
    with span("mark") as mark:
        pass
    return mark.id


def test_nesting_gives_parent_ids():
    mark = _last_id()
    with span("outer") as outer:
        with span("middle") as middle:
            with span("inner") as inner:
                pass
        with span("sibling") as sibling:
            pass
    got = _since(mark)
    assert got[outer.id][4] is None
    assert got[middle.id][4] == outer.id
    assert got[inner.id][4] == middle.id
    assert got[sibling.id][4] == outer.id
    # a parent encloses its children on the clock
    o, i = got[outer.id], got[inner.id]
    assert o[1] <= i[1] <= i[2] <= o[2]
    # finished spans are recorded in the order they close
    assert [sp[0] for sp in got.values()] == [
        "inner", "middle", "sibling", "outer"]


def test_attributes_are_kept_and_can_be_added_inside():
    mark = _last_id()
    with span("engine.admit", rid=7, prompt_len=12):
        pass
    with span("engine.tick") as sp:
        sp.attrs["active"] = 3
    admit, tick = _since(mark).values()
    assert admit[0] == "engine.admit"
    assert admit[5] == {"rid": 7, "prompt_len": 12}
    assert tick[5] == {"active": 3}


def test_a_span_closed_by_an_exception_is_recorded():
    mark = _last_id()
    try:
        with span("outer"):
            with span("failing"):
                raise KeyError("x")
    except KeyError:
        pass
    names = [sp[0] for sp in _since(mark).values()]
    assert names == ["failing", "outer"]
    with span("after") as after:
        pass
    assert _since(mark)[after.id][4] is None


def test_ring_keeps_its_bound():
    for _ in range(SPAN_RING + 5):
        with span("fill"):
            pass
    spans = recent_spans()
    assert len(spans) == SPAN_RING == monitor._spans.maxlen
    ids = [sp[3] for sp in spans]
    assert ids == sorted(ids) and ids[-1] - ids[0] == SPAN_RING - 1


def test_threads_nest_apart():
    mark = _last_id()
    ready, go = threading.Event(), threading.Event()
    ids = {}

    def worker():
        with span("worker") as w:
            ready.set()
            go.wait(10)
        ids["worker"] = w.id

    t = threading.Thread(target=worker)
    with span("main") as m:
        t.start()
        assert ready.wait(10)
        go.set()
        t.join(10)
    assert not t.is_alive()
    got = _since(mark)
    assert got[ids["worker"]][4] is None
    assert got[m.id][4] is None
