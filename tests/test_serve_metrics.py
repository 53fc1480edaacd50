"""Metrics registry: counter/gauge/histogram semantics, Prometheus
text exposition, and the engine/monitor threading (duck-typed — the
registry is handed in, never imported by launch/runtime)."""

import time

import numpy as np
import pytest

from repro.launch.serve import Request
from repro.runtime.monitor import StepMonitor
from repro.serve.metrics import (DEFAULT_LATENCY_BUCKETS, Counter, Gauge,
                                 Histogram, MetricsRegistry)
from serve_testlib import FakeEngine


class TestPrimitives:
    def test_counter_monotone(self):
        c = Counter("reqs")
        c.inc()
        c.inc(4, replica="1")
        assert c.value() == 1 and c.value(replica="1") == 4
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_set_inc_dec(self):
        g = Gauge("depth")
        g.set(5)
        g.inc(2)
        g.dec()
        assert g.value() == 6

    def test_histogram_buckets_and_quantile(self):
        h = Histogram("lat", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 1.7, 3.0, 9.0):
            h.observe(v)
        cell = h.labels()
        assert cell.counts == [1, 2, 1, 1]   # (..1], (1..2], (2..4], +Inf
        assert cell.count == 5 and cell.sum == pytest.approx(15.7)
        assert 0.0 < h.quantile(0.5) <= 2.0
        assert h.quantile(0.99) == 4.0       # +Inf clamps to last bound
        assert Histogram("e").quantile(0.5) == 0.0

    def test_histogram_needs_buckets(self):
        with pytest.raises(ValueError):
            Histogram("x", buckets=())


class TestRegistry:
    def test_get_or_create_same_object(self):
        reg = MetricsRegistry()
        assert reg.counter("a") is reg.counter("a")
        with pytest.raises(TypeError):
            reg.gauge("a")
        assert reg.get("missing") is None

    def test_exposition_format(self):
        reg = MetricsRegistry()
        reg.counter("serve_tokens", "decoded tokens").inc(3, replica="0")
        reg.gauge("serve_queue_depth").set(2, replica="0")
        h = reg.histogram("ttft", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        text = reg.expose()
        assert "# TYPE serve_tokens counter" in text
        assert 'serve_tokens_total{replica="0"} 3' in text
        assert 'serve_queue_depth{replica="0"} 2' in text
        assert 'ttft_bucket{le="0.1"} 1' in text
        assert 'ttft_bucket{le="1"} 2' in text
        assert 'ttft_bucket{le="+Inf"} 2' in text
        assert "ttft_count 2" in text
        assert text.endswith("\n")

    def test_default_buckets_sorted(self):
        assert list(DEFAULT_LATENCY_BUCKETS) == \
            sorted(DEFAULT_LATENCY_BUCKETS)


class TestEngineThreading:
    """The FakeEngine mirrors ServeEngine's metric call sites; the
    real-engine series names are asserted in test_serve_gateway's
    /metrics scrape and exercised by every metered pool test."""

    def test_real_engine_series(self):
        """ServeEngine itself publishes the serve_* series (smoke-size
        real engine, one request)."""
        jax = pytest.importorskip("jax")
        from repro.configs import get_smoke
        from repro.core.precision import PrecisionPolicy
        from repro.launch.serve import ServeEngine
        from repro.models import api

        reg = MetricsRegistry()
        cfg = get_smoke("gemma3-1b")
        eng = ServeEngine(cfg, batch_size=1, max_ctx=32,
                          policy=PrecisionPolicy.uniform("f32"),
                          max_queue=2, metrics=reg, replica="7")
        eng.load(api.init_params(jax.random.PRNGKey(0), cfg))
        req = Request(rid=0, prompt=np.arange(2, 6, dtype=np.int32),
                      max_new_tokens=3)
        eng.run([req])
        assert reg.counter("serve_tokens").value(replica="7") == \
            len(req.out_tokens)
        assert reg.counter(
            "serve_requests_submitted").value(replica="7") == 1
        assert reg.histogram("serve_ttft_seconds").count(replica="7") == 1
        assert reg.histogram("serve_tick_seconds").count(replica="7") >= 1
        assert reg.gauge("serve_slot_occupancy").value(replica="7") == 0.0
        text = reg.expose()
        assert "serve_inter_token_seconds_bucket" in text
        # rejection path increments the rejected counter
        eng.max_queue = 0
        with pytest.raises(Exception):
            eng.submit(Request(rid=1,
                               prompt=np.arange(2, 5, dtype=np.int32)))
        assert reg.counter(
            "serve_requests_rejected").value(replica="7") == 1


    def test_inter_token_gap_covers_an_admission_between_ticks(self):
        """``serve_inter_token_seconds`` is each request's gap since its
        own previous token: a prefill made between two of its ticks
        lands in the gap, and not in ``serve_tick_seconds``."""
        jax = pytest.importorskip("jax")
        from repro.configs import get_smoke
        from repro.core.precision import PrecisionPolicy
        from repro.launch.serve import ServeEngine
        from repro.models import api

        cfg = get_smoke("gemma3-1b")
        eng = ServeEngine(cfg, batch_size=2, max_ctx=32,
                          policy=PrecisionPolicy.uniform("f32"), eos_id=-1)
        eng.load(api.init_params(jax.random.PRNGKey(0), cfg))
        prompt = np.arange(2, 6, dtype=np.int32)
        eng.run([Request(rid=0, prompt=prompt, max_new_tokens=3)])  # warm
        reg = MetricsRegistry()
        eng.metrics = reg
        prefill = eng._prefill

        def slow_prefill(*a):
            time.sleep(0.3)
            return prefill(*a)

        eng._prefill = slow_prefill
        eng.submit(Request(rid=1, prompt=prompt, max_new_tokens=4))
        eng.step()     # admits rid 1, then ticks it
        eng.submit(Request(rid=2, prompt=prompt, max_new_tokens=4))
        eng.step()     # admits rid 2 (0.3 s) between two ticks of rid 1

        def above(name, x):
            cell = reg.histogram(name).labels(replica="0")
            return sum(c for b, c in zip((0.0,) + cell.bounds, cell.counts)
                       if b >= x)

        assert above("serve_inter_token_seconds", 0.25) == 1
        assert above("serve_tick_seconds", 0.25) == 0
        assert reg.histogram("serve_inter_token_seconds").count(
            replica="0") == 3   # rid 1 twice, rid 2 once


class TestFaultToleranceSeries:
    """The PR-9 observability contract: replica health and recovery are
    first-class series, published by the monitor/pool — asserted here
    on the FakeEngine pool so the names can't silently drift."""

    def _chaos_pool(self, plan, reg, **kw):
        from repro.serve.faults import FaultPlan
        from repro.serve.pool import ReplicaPool
        from serve_testlib import fake_factory
        return ReplicaPool(
            None, None, replicas=2, batch_size=2, metrics=reg,
            engine_factory=FaultPlan.parse(plan).wrap_factory(
                fake_factory(2, None), n_replicas=2), **kw)

    def test_replica_state_gauge_and_failure_counter(self):
        reg = MetricsRegistry()
        pool = self._chaos_pool("0:crash@2@r0", reg)
        reqs = [Request(rid=i, prompt=np.arange(3, dtype=np.int32),
                        max_new_tokens=8) for i in range(4)]
        pool.run(reqs)
        from repro.serve.health import ReplicaState
        assert reg.gauge("serve_replica_state").value(replica="0") == \
            int(ReplicaState.DEAD)
        assert reg.gauge("serve_replica_state").value(replica="1") == \
            int(ReplicaState.HEALTHY)
        assert reg.counter(
            "serve_replica_failures").value(replica="0") == 1

    def test_recovery_counter_and_latency_histogram(self):
        reg = MetricsRegistry()
        pool = self._chaos_pool("0:crash@3@r0", reg)
        reqs = [Request(rid=i, prompt=np.arange(3, dtype=np.int32),
                        max_new_tokens=8) for i in range(4)]
        pool.run(reqs)
        n_rec = len(pool.recovery_events)
        assert n_rec >= 1
        assert reg.counter("serve_requests_recovered").value() == n_rec
        h = reg.histogram("serve_recovery_ticks")
        assert h.count() == n_rec
        from repro.serve.metrics import TICK_BUCKETS
        assert h.quantile(0.99) <= TICK_BUCKETS[-1]
        text = reg.expose()
        assert "serve_recovery_ticks_bucket" in text

    def test_expired_counter(self):
        # sole replica crashes: the orphan can never land, so it must
        # terminate at its deadline through the pool-level expiry path
        reg = MetricsRegistry()
        from repro.serve.faults import FaultPlan
        from repro.serve.pool import ReplicaPool
        from serve_testlib import fake_factory
        pool = ReplicaPool(
            None, None, replicas=1, batch_size=2, metrics=reg,
            engine_factory=FaultPlan.parse("0:crash@2@r0").wrap_factory(
                fake_factory(2, None), n_replicas=1))
        req = Request(rid=0, prompt=np.arange(3, dtype=np.int32),
                      max_new_tokens=30, deadline_ticks=6)
        pool.run([req])
        assert req.expired
        assert reg.counter(
            "serve_requests_expired").value(replica="pool") == 1

    def test_tick_buckets_sorted(self):
        from repro.serve.metrics import TICK_BUCKETS
        assert list(TICK_BUCKETS) == sorted(TICK_BUCKETS)


class TestMonitorIntegration:
    def test_monitor_publishes(self):
        reg = MetricsRegistry()
        mon = StepMonitor(window=8, model_flops_per_step=1e12,
                          metrics=reg, name="train_step")
        for dt in (0.01, 0.02, 0.01, 0.015):
            mon.observe(dt)
        assert reg.histogram("train_step_time_seconds").count() == 4
        assert reg.gauge("train_step_achieved_tflops").value() > 0

    def test_fake_engine_accepts_registry(self):
        # the pool hands the registry through engine_factory untouched
        reg = MetricsRegistry()
        eng = FakeEngine(batch_size=1, metrics=reg)
        assert eng.metrics is reg
