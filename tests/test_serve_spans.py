"""The serve engine's spans on a CPU smoke run: every admission and
tick inside an engine step, and each blocking device-to-host read in an
``engine.sync`` span where it happens."""

import collections

import jax
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.precision import PrecisionPolicy
from repro.launch.serve import Request, ServeEngine
from repro.models import api
from repro.runtime.monitor import recent_spans, span


@pytest.fixture(scope="module")
def served():
    cfg = get_smoke("gemma3-1b")
    eng = ServeEngine(cfg, batch_size=2, max_ctx=32,
                      policy=PrecisionPolicy.uniform("f32"), eos_id=-1)
    eng.load(api.init_params(jax.random.PRNGKey(0), cfg))
    # three requests on two slots: the third waits, and is admitted
    # between ticks of the others
    reqs = [Request(rid=10 + i, prompt=np.arange(2, 2 + n, dtype=np.int32),
                    max_new_tokens=m)
            for i, (n, m) in enumerate([(4, 3), (6, 5), (5, 4)])]
    with span("mark") as mark:
        pass
    ticks0 = eng.ticks
    eng.run(reqs)
    spans = {sp[3]: sp for sp in recent_spans() if sp[3] > mark.id}
    return reqs, spans, eng.ticks - ticks0


def _children(spans):
    kids = collections.defaultdict(list)
    for sp in spans.values():
        kids[sp[4]].append(sp)
    return kids


def _named(spans, name):
    return [sp for sp in spans.values() if sp[0] == name]


def test_admissions_and_ticks_lie_inside_steps(served):
    _, spans, _ = served
    for sp in _named(spans, "engine.tick") + _named(spans, "engine.admit"):
        parent = spans[sp[4]]
        assert parent[0] == "engine.step"
        assert parent[1] <= sp[1] <= sp[2] <= parent[2]
    assert all(sp[4] is None for sp in _named(spans, "engine.step"))


def test_each_decoding_tick_reads_the_device_three_times(served):
    _, spans, ticks = served
    kids = _children(spans)
    decoded = [sp for sp in _named(spans, "engine.tick")
               if sp[5]["active"] > 0]
    assert len(decoded) == ticks
    for tick in decoded:
        syncs = [k[5]["what"] for k in kids[tick[3]]
                 if k[0] == "engine.sync"]
        assert syncs == ["active", "tokens", "finished"]
        assert [k[0] for k in kids[tick[3]]] == [
            "engine.sync", "engine.launch", "engine.sync", "engine.sync",
            "engine.drain"]
    assert all(k[0] == "engine.sync" for sp in _named(spans, "engine.tick")
               if sp[5]["active"] == 0 for k in kids[sp[3]])


def test_each_admission_reads_its_first_token_once(served):
    reqs, spans, _ = served
    kids = _children(spans)
    admits = _named(spans, "engine.admit")
    assert sorted(sp[5]["rid"] for sp in admits) == [r.rid for r in reqs]
    for sp in admits:
        assert sp[5]["prompt_len"] == len(
            next(r for r in reqs if r.rid == sp[5]["rid"]).prompt)
        assert [(k[0], k[5].get("what")) for k in kids[sp[3]]] == [
            ("engine.prefill", None), ("engine.sync", "first_token"),
            ("engine.splice", None)]
    # every read of the device is inside an admission or a tick
    for sp in _named(spans, "engine.sync"):
        assert spans[sp[4]][0] in ("engine.admit", "engine.tick")
