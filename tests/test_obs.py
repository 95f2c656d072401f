"""Observability layer tests: registry semantics, canonical snapshots, the
exporter-agreement invariant, disabled-mode tracing, span nesting/annotation,
the recompile tracker against real jitted compilations, the jax-free import
contract (subprocess with jax poisoned), and the obs_dump CLI.

The headline invariants, mirrored from ISSUE acceptance:
  * two dumps of equal registry state are BYTE-identical (canonical JSON);
  * the JSON snapshot round-trips through the Prometheus exporter's value
    set (one value set, two formats);
  * with no tracer installed, span() returns the one shared NULL_SPAN;
  * a fixed-shape jitted loop compiles exactly once per kernel, a
    shape-varying loop once per distinct shape.
"""
import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from consensus_specs_tpu.obs import export as obs_export  # noqa: E402
from consensus_specs_tpu.obs import metrics as obs_metrics  # noqa: E402
from consensus_specs_tpu.obs import recompile as obs_recompile  # noqa: E402
from consensus_specs_tpu.obs import trace as obs_trace  # noqa: E402
from consensus_specs_tpu.obs.metrics import MetricsRegistry, series_key  # noqa: E402


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    """Tracing/tracking is globally installed state (the FaultPlan pattern);
    never leak an installed tracer into another test module."""
    yield
    obs_trace.uninstall()
    obs_recompile.uninstall()


# --- registry ----------------------------------------------------------------


def test_series_key_canonical_and_escaped():
    assert series_key("x") == "x"
    assert series_key("x", {"b": 1, "a": "v"}) == 'x{a="v",b="1"}'
    # labels sorted -> identity independent of kwargs order
    r = MetricsRegistry()
    assert r.counter("c", a=1, b=2) is r.counter("c", b=2, a=1)
    assert series_key("x", {"a": 'q"\\'}) == 'x{a="q\\"\\\\"}'


def test_counter_gauge_basics():
    r = MetricsRegistry()
    c = r.counter("hits", route="rx")
    c.inc()
    c.inc(4)
    assert r.counter_value("hits", route="rx") == 5
    # reads never materialize series (snapshots must not depend on reads)
    assert r.counter_value("hits", route="never") == 0
    assert series_key("hits", {"route": "never"}) not in r.snapshot()["counters"]
    g = r.gauge("depth")
    g.set(3)
    g.add(2)
    assert r.gauge_value("depth") == 5
    assert r.counters_matching("hits") == {'hits{route="rx"}': 5}


def test_histogram_quantiles_and_buckets():
    r = MetricsRegistry()
    h = r.histogram("lat", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count == 5 and h.sum == pytest.approx(5.56)
    cum = h.cumulative_buckets()
    assert cum == [(0.01, 2), (0.1, 3), (1.0, 4), ("+Inf", 5)]
    assert 0.0 < h.quantile(0.5) <= 0.1
    # +Inf bucket resolves to the observed max, not infinity
    assert h.quantile(0.99) == 5.0
    assert h.quantile(0.0) <= h.quantile(1.0)


def test_registry_reset_keeps_handles_wired():
    r = MetricsRegistry()
    c = r.counter("n")
    c.inc(7)
    r.reset()
    assert r.counter_value("n") == 0
    c.inc()  # the cached handle still feeds the same series
    assert r.counter_value("n") == 1


# --- canonical snapshot + exporter agreement ---------------------------------


def _populated_registry() -> MetricsRegistry:
    r = MetricsRegistry()
    r.counter("fault_fires_total", site="engine.dispatch").inc(3)
    r.counter("retries_total", error="TransientFault").inc(2)
    r.gauge("bls_last_flush_items").set(128)
    r.gauge("bls_last_flush_path", path="rlc_grouped").set(1)
    h = r.histogram("span_seconds", span="engine.dispatch")
    for v in (1e-4, 2e-3, 0.6):
        h.observe(v)
    return r


def test_snapshot_byte_identical_across_dumps():
    r = _populated_registry()
    a = obs_export.json_snapshot(r, meta={"sha": "deadbeef"})
    b = obs_export.json_snapshot(r, meta={"sha": "deadbeef"})
    assert a == b  # byte-identical: no timestamps, sorted keys
    ok, reason = obs_export.validate_snapshot_text(a)
    assert ok, reason


def test_snapshot_read_order_independent():
    """Reading values between dumps must not change the dump (reads never
    materialize series)."""
    r = _populated_registry()
    a = obs_export.json_snapshot(r)
    r.counter_value("fault_fires_total", site="nonexistent.site")
    r.gauge_value("bls_last_flush_path", path="rlc")
    assert obs_export.json_snapshot(r) == a


def test_validate_rejects_non_canonical_text():
    r = _populated_registry()
    snap = json.loads(obs_export.json_snapshot(r))
    pretty = json.dumps(snap, indent=2, sort_keys=True) + "\n"
    ok, reason = obs_export.validate_snapshot_text(pretty)
    assert not ok and "canonical" in reason
    ok, reason = obs_export.validate_snapshot_text("not json at all")
    assert not ok and "JSON" in reason
    ok, reason = obs_export.validate_snapshot_text('{"version":99}\n')
    assert not ok and "version" in reason


def test_prometheus_round_trips_snapshot_value_set():
    """THE exporter-agreement invariant: both formats expose one value set."""
    r = _populated_registry()
    snap = obs_export.snapshot_dict(r)
    json_vals = obs_export.snapshot_value_set(snap)
    prom_vals = obs_export.prometheus_value_set(obs_export.prometheus_text(snap))
    assert json_vals == prom_vals
    # and the set is non-trivial: counters, gauges, bucket/sum/count series
    assert 'fault_fires_total{site="engine.dispatch"}' in json_vals
    assert any(k.startswith("span_seconds_bucket{") for k in json_vals)
    assert 'span_seconds_count{span="engine.dispatch"}' in json_vals


def test_prometheus_text_shape():
    text = obs_export.prometheus_text(obs_export.snapshot_dict(_populated_registry()))
    lines = text.splitlines()
    assert "# TYPE fault_fires_total counter" in lines
    assert "# TYPE span_seconds histogram" in lines
    assert any(l.startswith('span_seconds_bucket{span="engine.dispatch",le="+Inf"}')
               for l in lines)


# --- histogram exemplars (ISSUE 13) ------------------------------------------


def test_exemplars_off_snapshot_is_byte_identical():
    """The exemplar feature must be invisible until used: a registry whose
    histograms never received an exemplar snapshots to the EXACT bytes the
    pre-exemplar format produced (no empty "exemplars" keys)."""
    a = obs_export.json_snapshot(_populated_registry())
    assert '"exemplars"' not in a
    ok, reason = obs_export.validate_snapshot_text(a)
    assert ok, reason


def test_exemplar_links_fat_bucket_to_trace_id():
    r = _populated_registry()
    h = r.histogram("span_seconds", span="engine.dispatch")
    h.observe(0.7, exemplar="t00000042")  # lands near the p99 tail
    h.observe(1e-4)                        # exemplar-less: bucket unchanged
    snap = obs_export.snapshot_dict(r)
    ex = snap["histograms"]['span_seconds{span="engine.dispatch"}']["exemplars"]
    assert list(ex.values()) == ["t00000042"]
    (le,) = ex.keys()
    assert le == "+Inf" or float(le) >= 0.7
    # later observation into the same bucket replaces the exemplar
    h.observe(0.7, exemplar="t00000043")
    snap2 = obs_export.snapshot_dict(r)
    ex2 = snap2["histograms"][
        'span_seconds{span="engine.dispatch"}']["exemplars"]
    assert list(ex2.values()) == ["t00000043"]


def test_exemplars_are_json_only_and_exporters_still_agree():
    """Exemplars ride the JSON snapshot, never the Prometheus text, and
    the exporter-agreement value-set invariant is untouched by them."""
    r = _populated_registry()
    r.histogram("span_seconds", span="engine.dispatch").observe(
        0.5, exemplar="t00000007")
    snap = obs_export.snapshot_dict(r)
    prom = obs_export.prometheus_text(snap)
    assert "t00000007" not in prom and "exemplar" not in prom
    assert (obs_export.snapshot_value_set(snap)
            == obs_export.prometheus_value_set(prom))
    text = obs_export.json_snapshot(r)
    ok, reason = obs_export.validate_snapshot_text(text)
    assert ok, reason


def test_exemplars_cleared_by_reset():
    r = _populated_registry()
    h = r.histogram("span_seconds", span="engine.dispatch")
    h.observe(0.5, exemplar="t00000001")
    r.reset()
    assert '"exemplars"' not in obs_export.json_snapshot(r)


# --- tracing -----------------------------------------------------------------


def test_disabled_mode_returns_shared_null_span():
    assert obs_trace.current_tracer() is None
    sp = obs_trace.span("engine.dispatch", epoch=3)
    assert sp is obs_trace.NULL_SPAN
    assert obs_trace.span("other") is sp  # one shared instance, no allocation
    with sp as s:
        s.set(k=1)
        assert s.attrs == {}
    obs_trace.annotate(fault_sites="x")  # no-op, must not raise


def test_span_nesting_timing_and_attrs():
    reg = MetricsRegistry()
    tr = obs_trace.Tracer(registry=reg).install()
    try:
        with obs_trace.span("engine.run_epochs", k=2) as outer:
            assert tr.current() is outer
            with obs_trace.span("engine.dispatch") as inner:
                inner.set(epoch=7)
                obs_trace.annotate(fault_sites="engine.dispatch")
        done = tr.spans()
        assert [s["name"] for s in done] == ["engine.dispatch", "engine.run_epochs"]
        d, o = done
        assert d["parent"] == "engine.run_epochs" and d["depth"] == 1
        assert o["parent"] is None and o["depth"] == 0
        assert d["attrs"]["epoch"] == 7
        assert d["attrs"]["fault_sites"] == ["engine.dispatch"]
        assert d["duration"] >= 0.0 and d["status"] == "ok"
        assert reg.counter_value("span_total", span="engine.dispatch") == 1
        assert reg.histogram("span_seconds", span="engine.dispatch").count == 1
    finally:
        tr.uninstall()
    assert obs_trace.span("x") is obs_trace.NULL_SPAN


def test_span_error_status_and_counter():
    reg = MetricsRegistry()
    tr = obs_trace.Tracer(registry=reg).install()
    try:
        with pytest.raises(ValueError):
            with obs_trace.span("bridge.dispatch"):
                raise ValueError("boom")
        (sp,) = tr.spans("bridge.dispatch")
        assert sp["status"] == "error" and sp["attrs"]["exc"] == "ValueError"
        assert reg.counter_value("span_errors_total", span="bridge.dispatch") == 1
    finally:
        tr.uninstall()


def test_span_ring_is_bounded_with_drop_counter():
    reg = MetricsRegistry()
    tr = obs_trace.Tracer(registry=reg, max_spans=5).install()
    try:
        for i in range(9):
            with obs_trace.span("s", i=i):
                pass
        assert len(tr.finished) == 5
        assert tr.dropped == 4
        assert reg.counter_value("spans_dropped_total") == 4
        # oldest dropped first: the survivors are the last five
        assert [s["attrs"]["i"] for s in tr.spans()] == [4, 5, 6, 7, 8]
        # the COUNTERS saw every span — the ring bounds memory, not accounting
        assert reg.counter_value("span_total", span="s") == 9
    finally:
        tr.uninstall()


def test_annotate_appends_known_list_keys_overwrites_others():
    tr = obs_trace.Tracer(registry=MetricsRegistry()).install()
    try:
        with obs_trace.span("engine.dispatch"):
            obs_trace.annotate(fault_sites="a", attempt=1)
            obs_trace.annotate(fault_sites="b", attempt=2)
        (sp,) = tr.spans()
        assert sp["attrs"]["fault_sites"] == ["a", "b"]
        assert sp["attrs"]["attempt"] == 2
    finally:
        tr.uninstall()


# --- profiler bridge -----------------------------------------------------------


def _profiled(tmp_path, body):
    """Run `body` under a CPU profiler trace; the host events of the trace
    as {name: [(line, start_ns, end_ns)]}."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                events.setdefault(e.name, []).append(
                    (line.name, e.start_ns, e.start_ns + e.duration_ns))
    return events


def test_spans_reach_the_profiler_trace_nested(tmp_path):
    """An installed tracer writes each span as a profiler host annotation
    of the same name, nested as the spans are, on the profiler's clock."""
    import jax  # noqa: F401  (the bridge binds only where jax is loaded)

    tr = obs_trace.Tracer(registry=MetricsRegistry()).install()
    assert tr.profiler_annotations

    def body():
        with obs_trace.span("bridge.outer"):
            with obs_trace.span("bridge.inner"):
                pass
            with obs_trace.span("bridge.inner"):
                pass

    try:
        events = _profiled(tmp_path, body)
    finally:
        tr.uninstall()
    (outer,) = events["bridge.outer"]
    inner = events["bridge.inner"]
    assert len(inner) == 2
    assert all(line == outer[0] and outer[1] <= s <= e <= outer[2]
               for line, s, e in inner)
    assert inner[0][2] <= inner[1][1]  # siblings in order, not overlapping
    # the span ring still times on the monotonic clock
    assert [s["name"] for s in tr.spans()] == ["bridge.inner", "bridge.inner",
                                               "bridge.outer"]


def test_no_tracer_writes_no_annotation(tmp_path):
    """Disabled mode stays the shared no-op: nothing reaches the trace."""
    assert obs_trace.current_tracer() is None

    def body():
        with obs_trace.span("bridge.disabled") as sp:
            assert sp is obs_trace.NULL_SPAN

    assert "bridge.disabled" not in _profiled(tmp_path, body)


def test_tracer_without_jax_records_spans_and_imports_nothing():
    """Installing a tracer where jax was never imported keeps the process
    jax-free (no poisoning here: an import would succeed, and be seen)."""
    code = """
import sys

from consensus_specs_tpu.obs import trace

tr = trace.Tracer().install()
assert not tr.profiler_annotations
with trace.span("engine.dispatch"):
    with trace.span("engine.aux_readout"):
        pass
tr.uninstall()
assert [s["name"] for s in tr.spans()] == ["engine.aux_readout", "engine.dispatch"]
assert "jax" not in sys.modules
print("TRACER-NO-JAX-OK")
"""
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, cwd=REPO)
    assert res.returncode == 0, res.stderr
    assert "TRACER-NO-JAX-OK" in res.stdout


# --- LAST_FLUSH compatibility view -------------------------------------------


def test_last_flush_view_is_registry_backed():
    from consensus_specs_tpu.crypto import bls_jax

    bls_jax.record_flush("rlc_grouped", items=16, distinct=4, miller_loops=5)
    assert bls_jax.LAST_FLUSH["path"] == "rlc_grouped"
    assert bls_jax.LAST_FLUSH["items"] == 16
    assert bls_jax.LAST_FLUSH["distinct"] == 4
    assert bls_jax.LAST_FLUSH["miller_loops"] == 5
    assert dict(bls_jax.LAST_FLUSH) == {
        "path": "rlc_grouped", "items": 16, "distinct": 4, "miller_loops": 5}
    assert len(bls_jax.LAST_FLUSH) == 4 and "path" in bls_jax.LAST_FLUSH
    # a second flush flips the one-hot path gauges; the view follows
    bls_jax.record_flush("rlc", items=3, distinct=3, miller_loops=4)
    assert bls_jax.LAST_FLUSH["path"] == "rlc"
    assert bls_jax.LAST_FLUSH["miller_loops"] == 4
    # the registry saw BOTH flushes cumulatively, not just the last
    reg = obs_metrics.REGISTRY
    assert reg.counter_value("bls_flush_total", path="rlc_grouped") >= 1
    assert reg.counter_value("bls_flush_total", path="rlc") >= 1


# --- recompile tracker -------------------------------------------------------


def test_recompile_fixed_shape_compiles_once():
    import jax
    import jax.numpy as jnp

    reg = MetricsRegistry()
    tracker = obs_recompile.CompileTracker(registry=reg).install()
    try:
        @jax.jit
        def _obs_fixed_kernel(x):
            return x * 2 + 1

        x = jnp.arange(16, dtype=jnp.int32)
        for _ in range(5):
            _obs_fixed_kernel(x).block_until_ready()
        assert tracker.compiles("_obs_fixed_kernel") == 1
        assert tracker.distinct_shapes("_obs_fixed_kernel") == 1
        assert reg.counter_value("compile_total", kernel="_obs_fixed_kernel") == 1
        assert tracker.kernel_seconds()["_obs_fixed_kernel"] > 0
    finally:
        tracker.uninstall()


def test_recompile_varying_shapes_compile_per_shape():
    import jax
    import jax.numpy as jnp

    reg = MetricsRegistry()
    tracker = obs_recompile.CompileTracker(registry=reg).install()
    try:
        @jax.jit
        def _obs_vary_kernel(x):
            return x + x

        for n in (8, 16, 32, 8, 16):  # 3 distinct shapes, 2 cache hits
            _obs_vary_kernel(jnp.zeros(n, dtype=jnp.int32)).block_until_ready()
        assert tracker.compiles("_obs_vary_kernel") == 3
        assert tracker.distinct_shapes("_obs_vary_kernel") == 3
        assert reg.gauge_value("compile_distinct_shapes",
                               kernel="_obs_vary_kernel") == 3
        assert "_obs_vary_kernel" in tracker.kernels()
    finally:
        tracker.uninstall()


def test_recompile_uninstall_stops_counting():
    import jax
    import jax.numpy as jnp

    tracker = obs_recompile.CompileTracker(registry=MetricsRegistry()).install()
    tracker.uninstall()

    @jax.jit
    def _obs_after_uninstall(x):
        return x - 1

    _obs_after_uninstall(jnp.ones(4, dtype=jnp.int32)).block_until_ready()
    assert tracker.compiles("_obs_after_uninstall") == 0


# --- jax-free import contract ------------------------------------------------


def test_obs_importable_without_jax():
    """The whole obs surface — registry, tracer, exporters, and a degraded
    CompileTracker.install() — must work in a process where jax cannot
    import (the runtime twin of tpulint's import-layering obs/ entry)."""
    code = """
import sys


class _Block:
    def find_spec(self, name, path=None, target=None):
        if name == "jax" or name.startswith("jax."):
            raise ImportError(f"poisoned for test: {name}")
        return None


sys.meta_path.insert(0, _Block())

from consensus_specs_tpu import obs
from consensus_specs_tpu.obs import trace, recompile

obs.REGISTRY.counter("fault_fires_total", site="engine.dispatch").inc()
with trace.span("engine.dispatch"):
    pass  # disabled mode: NULL_SPAN
tr = trace.Tracer().install()
with trace.span("engine.dispatch", epoch=1):
    trace.annotate(fault_sites="engine.dispatch")
tr.uninstall()
tracker = recompile.CompileTracker().install()  # degrades to a no-op sink
tracker.uninstall()
text = obs.json_snapshot()
ok, reason = obs.validate_snapshot_text(text)
assert ok, reason
assert not any(m == "jax" or m.startswith("jax.") for m in sys.modules)
print("OBS-NO-JAX-OK")
"""
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, cwd=REPO)
    assert res.returncode == 0, res.stderr
    assert "OBS-NO-JAX-OK" in res.stdout


# --- obs_dump CLI ------------------------------------------------------------


def _run_dump(*args):
    return subprocess.run(
        [sys.executable, str(REPO / "tools" / "obs_dump.py"), *args],
        capture_output=True, text=True, timeout=120, cwd=REPO)


def test_obs_dump_check_and_render(tmp_path):
    r = _populated_registry()
    path = tmp_path / "snap.json"
    obs_export.write_snapshot(path, r, meta={"lane": "test"})
    res = _run_dump("check", str(path))
    assert res.returncode == 0, res.stderr
    assert "OK" in res.stdout
    res = _run_dump("prom", str(path))
    assert res.returncode == 0
    assert "# TYPE fault_fires_total counter" in res.stdout
    res = _run_dump("table", str(path))
    assert res.returncode == 0
    assert "fault_fires_total" in res.stdout and "histogram" in res.stdout


def test_obs_dump_table_groups_by_subsystem_prefix(tmp_path):
    """Table mode groups series under [prefix] headers (sched_*, bls_*,
    fault_*, ...) in sorted group order, with canonical counter -> gauge ->
    histogram ordering preserved inside each group — pinned against the
    canonical snapshot so a renderer regression reorders loudly."""
    r = _populated_registry()
    r.counter("sched_submitted_total", work_class="bls", kind="verify").inc(4)
    r.gauge("sched_queue_depth", work_class="bls").set(2)
    r.histogram("sched_submit_latency_seconds", work_class="bls").observe(0.01)
    r.counter("gossip_rx_total", topic="attestation").inc(7)
    path = tmp_path / "snap.json"
    obs_export.write_snapshot(path, r, meta={"lane": "test"})
    res = _run_dump("table", str(path))
    assert res.returncode == 0, res.stderr
    lines = res.stdout.splitlines()
    headers = [ln for ln in lines if ln.startswith("[")]
    assert headers == ["[bls]", "[fault]", "[gossip]", "[retries]",
                       "[sched]", "[span]"]

    def block(header):
        start = lines.index(header) + 1
        out = []
        for ln in lines[start:]:
            if not ln.startswith("  "):
                break
            out.append(ln.split()[0])
        return out

    assert block("[sched]") == [
        'sched_submitted_total{kind="verify",work_class="bls"}',
        'sched_queue_depth{work_class="bls"}',
        'sched_submit_latency_seconds{work_class="bls"}',
    ]
    assert block("[gossip]") == ['gossip_rx_total{topic="attestation"}']
    # every series line is indented under some group header
    body = [ln for ln in lines if ln and not ln.startswith(("[", "meta:"))]
    assert all(ln.startswith("  ") for ln in body)


def test_obs_dump_table_groups_proof_series(tmp_path):
    """The read lane's proof_* series (PR 15 cache + service) group under
    one [proof] header with counter -> gauge -> histogram ordering — the
    prefix grouping must keep absorbing new subsystems with no renderer
    change."""
    r = MetricsRegistry()
    r.counter("proof_requests_total").inc(12)
    r.counter("proof_cache_hits_total", column="balances").inc(8)
    r.counter("proof_cache_misses_total", column="balances").inc(4)
    r.counter("proof_cache_invalidated_total", column="balances").inc(2)
    r.gauge("proof_cache_hit_ratio").set(8 / 12)
    r.gauge("proof_cache_entries").set(6)
    r.histogram("proof_request_latency_seconds").observe(0.002)
    r.counter("sched_submitted_total", work_class="merkle",
              kind="multiproof").inc(4)
    path = tmp_path / "snap.json"
    obs_export.write_snapshot(path, r, meta={"lane": "proofs"})
    res = _run_dump("table", str(path))
    assert res.returncode == 0, res.stderr
    lines = res.stdout.splitlines()
    headers = [ln for ln in lines if ln.startswith("[")]
    assert headers == ["[proof]", "[sched]"]
    start = lines.index("[proof]") + 1
    block = []
    for ln in lines[start:]:
        if not ln.startswith("  "):
            break
        block.append(ln.split()[0])
    assert block == [
        'proof_cache_hits_total{column="balances"}',
        'proof_cache_invalidated_total{column="balances"}',
        'proof_cache_misses_total{column="balances"}',
        "proof_requests_total",
        "proof_cache_entries",
        "proof_cache_hit_ratio",
        "proof_request_latency_seconds",
    ]


def test_obs_dump_table_top_ranks_hottest_first(tmp_path):
    """--top N drops the grouping: counters/gauges ranked by value,
    histograms by p99, truncated to N each — the incident view."""
    r = MetricsRegistry()
    r.counter("cold_total").inc(1)
    r.counter("warm_total").inc(50)
    r.counter("hot_total").inc(900)
    r.gauge("depth").set(70)
    r.histogram("fast_seconds").observe(1e-4)
    r.histogram("slow_seconds").observe(2.0)
    path = tmp_path / "snap.json"
    obs_export.write_snapshot(path, r)
    res = _run_dump("table", str(path), "--top", "2")
    assert res.returncode == 0, res.stderr
    lines = res.stdout.splitlines()
    assert lines[0] == "[top 2 counters/gauges by value]"
    scalar_keys = [ln.split()[0] for ln in lines[1:3]]
    assert scalar_keys == ["hot_total", "depth"]  # 900, then 70; cold cut
    assert "cold_total" not in res.stdout
    hix = lines.index("[top 2 histograms by p99]")
    hist_keys = [ln.split()[0] for ln in lines[hix + 1:hix + 3]]
    assert hist_keys == ["slow_seconds", "fast_seconds"]
    assert "p99=" in lines[hix + 1]
    # top larger than the series count: everything, still ranked
    res_all = _run_dump("table", str(path), "--top", "99")
    assert res_all.returncode == 0
    assert "cold_total" in res_all.stdout


def test_obs_dump_check_fails_loudly_on_corruption(tmp_path):
    path = tmp_path / "snap.json"
    path.write_text('{"version":1}\n')
    res = _run_dump("check", str(path))
    assert res.returncode == 1
    assert "INVALID" in res.stderr
    # non-canonical bytes (a sneaky space) are rejected too
    r = _populated_registry()
    good = obs_export.json_snapshot(r)
    (tmp_path / "pretty.json").write_text(good.replace('":', '": ', 1))
    res = _run_dump("check", str(tmp_path / "pretty.json"))
    assert res.returncode == 1 and "canonical" in res.stderr
    res = _run_dump("check", str(tmp_path / "missing.json"))
    assert res.returncode == 2
