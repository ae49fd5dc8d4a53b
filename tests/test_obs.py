"""repro.obs tier-1 shard: spans, metrics, and the communication ledger.

What is pinned here:

  * the ledger's measured collective bytes equal the DIRECT HLO-audit
    numbers (``roofline/hlo.collective_bytes_of`` on the same executable)
    exactly — on the pinned (8,1,1) / (2,2,2) streaming schedules and the
    fused two-grid regime-1 pair the PR 4/5 tests audit;
  * tracer + ledger overhead on the jitted ragged-update hot path stays
    under 2% of the untraced wall time;
  * the Prometheus text exposition against a golden file;
  * drift-flag -> autotune revalidation (property-tested flag predicate);
  * cross-thread span parenting through the async ingest queue;
  * collective-permute / all-to-all byte classification on captured HLO
    snippets (including identity-only routing no-ops and async -start
    forms).
"""
import contextlib
import json
import math
import pathlib
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hypothesis_compat import given, settings, st
from dist_helper import run_distributed

from repro import obs
from repro.obs import ledger as obs_ledger
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.roofline.hlo import collective_bytes_of

GOLDEN = pathlib.Path(__file__).parent / "golden" / "obs_prometheus.txt"


@pytest.fixture(autouse=True)
def _clean_obs():
    """Tracer/ledger are process-global and off by default — guarantee
    every test starts and ends uninstalled."""
    obs.uninstall_observability()
    yield
    obs.uninstall_observability()


@contextlib.contextmanager
def fresh_metrics():
    """Swap in an isolated MetricsRegistry (the default one is process-
    global and always on)."""
    prev = obs_metrics.get_metrics()
    reg = obs_metrics.MetricsRegistry()
    obs_metrics.set_metrics(reg)
    try:
        yield reg
    finally:
        obs_metrics.set_metrics(prev)


class _FakeFn:
    """Quacks like a jitted function for CommLedger.observe: .lower()
    .compile().as_text() returns a canned HLO module text."""

    def __init__(self, text: str):
        self._text = text

    def lower(self, *args):
        return self

    def compile(self):
        return self

    def as_text(self):
        return self._text


# one moving all-reduce of a f32[16,8] = 512-byte operand
_AR_512 = """
HloModule m, num_partitions=4
%p0 = f32[16,8]{1,0} parameter(0)
%ar = f32[16,8]{1,0} all-reduce(%p0), replica_groups={{0,1,2,3}}
"""


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_counter_basic():
    c = obs_metrics.Counter("c_total")
    c.inc()
    c.inc(2.5)
    c.inc(3, path="ragged")
    assert c.value() == 3.5
    assert c.value(path="ragged") == 3
    assert c.value(path="other") == 0.0
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_inc_dec():
    g = obs_metrics.Gauge("g")
    g.set(5)
    g.inc(2)
    g.dec(3)
    assert g.value() == 4.0
    g.set(1, queue="a")
    assert g.value(queue="a") == 1.0
    assert g.value() == 4.0


def test_histogram_percentile_matches_numpy():
    h = obs_metrics.Histogram("h", buckets=(1.0, 10.0))
    assert h.percentile(50) == 0.0          # empty window: never raises
    rng = np.random.default_rng(0)
    xs = rng.exponential(0.01, size=257)
    for x in xs:
        h.observe(float(x))
    for q in (0, 50, 90, 99, 100):
        assert h.percentile(q) == pytest.approx(np.percentile(xs, q),
                                                rel=1e-12)
    assert h.count() == 257


def test_histogram_window_stays_bounded():
    h = obs_metrics.Histogram("h", buckets=(1.0,))
    n = obs_metrics._RAW_WINDOW + 100
    for i in range(n):
        h.observe(float(i))
    st_ = h._states[()]
    assert st_.count == n                   # totals never truncate
    assert len(st_.window) <= obs_metrics._RAW_WINDOW
    # the window keeps the most recent values, so high quantiles track
    assert h.percentile(100) == float(n - 1)


def test_registry_kind_clash_and_names():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("a_total")
    with pytest.raises(TypeError):
        reg.gauge("a_total")
    reg.gauge("b")
    assert list(reg.names()) == ["a_total", "b"]
    assert reg.counter("a_total") is reg.counter("a_total")


def test_prometheus_exposition_golden():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("requests_total")
    c.inc()
    c.inc(2, path="ragged")
    reg.gauge("queue_depth").set(3)
    h = reg.histogram("lat_seconds", buckets=(0.5, 2.0))
    for v in (0.25, 0.5, 4.0):              # le is inclusive: 0.5 in-bucket
        h.observe(v)
    assert reg.prometheus_text() == GOLDEN.read_text()


def test_prometheus_empty_registry_and_zero_series():
    reg = obs_metrics.MetricsRegistry()
    assert reg.prometheus_text() == ""
    reg.counter("n_total")                  # registered, never incremented
    assert "n_total 0" in reg.prometheus_text()


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_span_nesting_and_ids():
    t = obs.install_tracer()
    with obs_trace.span("outer", cat="t") as a:
        assert obs_trace.current_span_id() == a.span_id
        with obs_trace.span("inner", cat="t", k=3) as b:
            assert b.parent == a.span_id
    assert obs_trace.current_span_id() is None
    names = {s.name: s for s in t.spans}
    assert names["inner"].parent_id == names["outer"].span_id
    assert names["outer"].parent_id is None
    assert names["inner"].args == {"k": 3}
    assert names["inner"].dur_ns >= 0


def test_chrome_export(tmp_path):
    t = obs.install_tracer()
    with obs_trace.span("a", cat="c", n=7):
        with obs_trace.span("b"):
            pass
    path = t.export_chrome(str(tmp_path / "trace.json"))
    doc = json.loads(pathlib.Path(path).read_text())
    evs = {e["name"]: e for e in doc["traceEvents"]}
    assert evs["a"]["ph"] == "X" and evs["a"]["cat"] == "c"
    assert evs["b"]["cat"] == "repro"       # empty cat gets a default
    assert evs["a"]["args"]["n"] == 7
    assert evs["b"]["args"]["parent_id"] == evs["a"]["args"]["span_id"]
    assert evs["a"]["dur"] >= evs["b"]["dur"] >= 0


def test_max_spans_bound():
    t = obs.install_tracer(obs.Tracer(max_spans=2))
    for i in range(4):
        with obs_trace.span(f"s{i}"):
            pass
    assert len(t.spans) == 2 and t.dropped == 2
    t.clear()
    assert t.spans == [] and t.dropped == 0


def test_span_is_shared_noop_when_uninstalled():
    assert obs_trace.get_tracer() is None
    c1 = obs_trace.span("a")
    c2 = obs_trace.span("b", cat="x", k=1)
    assert c1 is c2                         # one shared nullcontext
    with c1:
        assert obs_trace.current_span_id() is None


def test_cross_thread_explicit_parent():
    t = obs.install_tracer()
    with obs_trace.span("submit") as ctx:
        parent = obs_trace.current_span_id()
        assert parent == ctx.span_id
    th = threading.Thread(
        target=lambda: obs_trace.span("apply", parent=parent).__enter__()
        .__exit__(None, None, None))
    th.start()
    th.join()
    names = {s.name: s for s in t.spans}
    assert names["apply"].parent_id == names["submit"].span_id
    assert names["apply"].tid != names["submit"].tid


def _profiled(tmp_path, fn):
    """Run ``fn`` under a JAX profiler session on the CPU; returns the
    host events of the written profile as (line, name, start, end), where
    a line is one thread's."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    events = []
    for p, plane in enumerate(ProfileData.from_file(str(path)).planes):
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    events.append(((p, i), ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns))
    return events


def _inside(inner, outer):
    return outer[2] <= inner[2] and inner[3] <= outer[3]


def test_spans_land_in_the_profile_with_no_tracer(tmp_path):
    from repro.plan import plan_sketch
    plan = plan_sketch(32, 16, 8, P=1)
    A = np.ones((32, 16), np.float32)
    jax.block_until_ready(plan.execute(A))          # compile outside
    svc, sids, q = _local_service_and_queue(n_streams=1)

    def work():
        with jax.profiler.TraceAnnotation("test.request"):
            jax.block_until_ready(plan.execute(A))
            with q:
                q.submit(sids[0], np.ones((4, 16), np.float32), 0)
                q.flush(raise_errors=True)

    assert obs_trace.get_tracer() is None
    events = _profiled(tmp_path, work)
    (outer,) = [e for e in events if e[1] == "test.request"]
    (execute,) = [e for e in events if e[1] == "plan.execute"]
    rounds = [e for e in events if e[1] == "ingest.apply_round"]
    assert execute[0] == outer[0] and _inside(execute, outer)
    assert rounds and all(_inside(e, outer) for e in rounds)
    assert all(e[0] != outer[0] for e in rounds)    # the worker's thread


def test_span_is_recorded_and_profiled_with_a_tracer(tmp_path):
    t = obs.install_tracer()

    def work():
        with obs_trace.span("outer.op", cat="t", n=3):
            with obs_trace.span("inner.op"):
                pass

    events = _profiled(tmp_path, work)
    assert [s.name for s in t.spans] == ["inner.op", "outer.op"]
    assert t.spans[1].args == {"n": 3}
    (outer,) = [e for e in events if e[1] == "outer.op"]
    (inner,) = [e for e in events if e[1] == "inner.op"]
    assert _inside(inner, outer)


def test_plan_execute_observes_its_host_time():
    from repro.plan import plan_sketch
    plan = plan_sketch(32, 16, 8, P=1)
    labels = {"task": "sketch", "variant": plan.variant}
    with fresh_metrics() as reg:
        for calls in (1, 2, 3):
            plan.execute(np.ones((32, 16), np.float32))
            hist = reg.histogram("plan_execute_seconds")
            assert hist.count(**labels) == calls
        assert hist.labelsets() == [labels]
        assert hist.percentile(50, **labels) > 0
        assert "plan_execute_seconds_count" in reg.prometheus_text()


def test_omega_counters_match_the_kernel_grid():
    """Generated entries are each distinct (k, j) Omega tile once: three
    row blocks of 256 (n1 = 520, padded to 768) share the VMEM panel the
    first one fills, so the launch generates the n2·r entries the
    product uses."""
    from repro.kernels.ops import sketch_matmul
    from repro.plan import plan_sketch
    n1, n2, r = 520, 96, 16
    plan = plan_sketch(n1, n2, r, P=1, allow_pallas=True)
    assert plan.variant == "pallas_fused"
    A = jnp.ones((n1, n2), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda a: sketch_matmul(
        a, seed=3, r=r, interpret=True, **plan.blocks))(A)
    (call,) = [e for e in _eqns(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"]
    gm = call.params["grid_mapping"]
    bk = gm.block_mappings[0].block_shape[1].block_size    # A's (bm, bk)
    bn = gm.block_mappings[-1].block_shape[1].block_size   # B's (bm, bn)
    nj, ni, nk = gm.grid
    assert ni == 3 and gm.scratch_avals[-1].shape == (nk * bk, bn)
    with fresh_metrics() as reg:
        B = plan.execute(A, seed=3)
        assert B.shape == (n1, r)
        gen = reg.counter("omega_entries_generated_total")
        need = reg.counter("omega_entries_needed_total")
        assert gen.value(kernel="sketch_a_omega") == \
            nj * nk * bk * bn == n2 * r
        assert need.value(kernel="sketch_a_omega") == n2 * r


# case -> (kernel, its wrapper, operand shape, axis): the operand's block
# has the contraction bk on ``axis``, the output's block the Omega tile's
# other side.  sketch_a_omega on three row blocks keeps its Omega panel,
# on one it generates a tile a step.  sketch_omega_t_b covers B with one
# column block, also where B is wider than 128 columns.
_OMEGA_LAUNCHES = {
    "sketch_a_omega": ("sketch_a_omega", "sketch_matmul", (520, 96), 1),
    "sketch_a_omega-one-row-block": ("sketch_a_omega", "sketch_matmul",
                                     (200, 96), 1),
    "sketch_omega_t_b": ("sketch_omega_t_b", "sketch_t_matmul", (96, 40), 0),
    "sketch_omega_t_b-wide": ("sketch_omega_t_b", "sketch_t_matmul",
                              (96, 200), 0),
}


@pytest.mark.parametrize("case", sorted(_OMEGA_LAUNCHES))
def test_omega_counters_match_each_kernel_grid(case):
    """Each eager launch counts the Omega tiles its pallas_call generates,
    read off that call: grid steps times the (bk, block) tile, or, where
    the kernel keeps a panel (a second scratch), the steps of one row
    block."""
    from repro.kernels import ops
    kernel, wrapper, shape, axis = _OMEGA_LAUNCHES[case]
    fn = getattr(ops, wrapper)
    X = jnp.ones(shape, jnp.float32)
    r = 16
    jaxpr = jax.make_jaxpr(lambda x: fn(x, seed=5, r=r, interpret=True))(X)
    (call,) = [e for e in _eqns(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"]
    gm = call.params["grid_mapping"]
    out_block = gm.block_mappings[-1].block_shape
    bk = gm.block_mappings[0].block_shape[axis].block_size
    tile = bk * out_block[axis].block_size
    steps = math.prod(gm.grid)
    if gm.num_scratch_operands == 2:                # the Omega panel
        steps //= gm.grid[1]
    assert (gm.num_scratch_operands == 2) == (case == "sketch_a_omega")
    if kernel == "sketch_omega_t_b":
        assert gm.grid[1] == 1                      # one column block of B
    with fresh_metrics() as reg:
        for calls in (1, 2):
            fn(X, seed=5, r=r, interpret=True)
            gen = reg.counter("omega_entries_generated_total")
            need = reg.counter("omega_entries_needed_total")
            assert gen.value(kernel=kernel) == calls * steps * tile
            assert need.value(kernel=kernel) == calls * shape[axis] * r
        assert set(gen.snapshot()) == {f'{{kernel="{kernel}"}}'}


def test_nystrom_fused_plan_counts_both_kernels():
    from repro.kernels.ops import sketch_matmul_launch, sketch_t_matmul_launch
    from repro.plan import plan_nystrom
    n, r = 96, 16
    plan = plan_nystrom(n, r, P=1, allow_pallas=True)
    assert plan.variant == "pallas_fused"
    with fresh_metrics() as reg:
        plan.execute(jnp.ones((n, n), jnp.float32), seed=2)
        gen = reg.counter("omega_entries_generated_total")
        need = reg.counter("omega_entries_needed_total")
        a = sketch_matmul_launch(n, n, r, **plan.blocks)
        t = sketch_t_matmul_launch(n, r, r)
        assert gen.value(kernel="sketch_a_omega") == a.generated
        assert gen.value(kernel="sketch_omega_t_b") == t.generated
        assert need.value(kernel="sketch_a_omega") == n * r
        assert need.value(kernel="sketch_omega_t_b") == n * r


def test_nystrom_fused_stages_are_spans_under_plan_execute(tmp_path):
    """Each stage's dispatch is a span inside ``plan.execute``, in the
    tracer and in a profile, with its shapes and blocks."""
    from repro.kernels.ops import sketch_matmul_launch, sketch_t_matmul_launch
    from repro.plan import plan_nystrom
    n, r = 96, 16
    plan = plan_nystrom(n, r, P=1, allow_pallas=True)
    assert plan.variant == "pallas_fused"
    A = jnp.ones((n, n), jnp.float32)
    t = obs.install_tracer()
    events = _profiled(tmp_path, lambda: jax.block_until_ready(
        plan.execute(A, seed=2)))
    spans = {s.name: s for s in t.spans}
    launches = {"nystrom.stage1": sketch_matmul_launch(n, n, r,
                                                       **plan.blocks),
                "nystrom.stage2": sketch_t_matmul_launch(n, r, r)}
    (outer,) = [e for e in events if e[1] == "plan.execute"]
    stages = []
    for name, launch in launches.items():
        assert spans[name].parent_id == spans["plan.execute"].span_id
        assert spans[name].args == {"n": n, "r": r,
                                    "blocks": list(launch.blocks)}
        (event,) = [e for e in events if e[1] == name]
        assert event[0] == outer[0] and _inside(event, outer)
        stages.append(event)
    assert stages[0][3] <= stages[1][2]


def test_omega_launch_traced_into_a_program_is_not_counted():
    """A launch inside an enclosing jit runs on every call of that
    program, which host code does not see: it publishes nothing."""
    from repro.kernels.ops import sketch_matmul
    with fresh_metrics() as reg:
        f = jax.jit(lambda a: 2 * sketch_matmul(a, seed=1, r=8,
                                                interpret=True))
        f(jnp.ones((64, 32), jnp.float32))
        assert "omega_entries_generated_total" not in reg.names()
        sketch_matmul(jnp.ones((64, 32), jnp.float32), seed=1, r=8,
                      interpret=True)
        assert "omega_entries_generated_total" in reg.names()


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            sub = getattr(v, "jaxpr", v)        # a ClosedJaxpr, or a Jaxpr
            if hasattr(sub, "eqns"):
                yield from _eqns(sub)


# ---------------------------------------------------------------------------
# ledger
# ---------------------------------------------------------------------------

def test_observe_accumulates_per_signature():
    led = obs.install_ledger()
    fn = jax.jit(lambda x: x * 2)
    x = jnp.ones((4, 4), jnp.float32)
    led.observe("t.op", fn, (x,))
    led.observe("t.op", fn, (x,), wall_s=0.5)
    assert len(led) == 1
    site = led.site("t.op")
    assert site.calls == 2 and site.wall_s == 0.5
    # single-device executable: zero collective bytes, at a zero floor
    assert site.measured_bytes_per_call == 0.0
    assert site.bound_fraction == 1.0 and site.drift == 0.0
    led.observe("t.op", fn, (jnp.ones((8, 4)),))
    assert len(led) == 2                    # new signature, new site


def test_observe_scalar_arg_with_committed_sharding():
    """Regression: a 0-d operand committed to one device (jnp.int32 row
    offset) must not pin the lazy re-lowering — only mesh (Named)
    shardings constrain it."""
    led = obs.install_ledger()
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    a = jax.device_put(jnp.ones((4, 4)), NamedSharding(mesh, P("x", None)))
    r0 = jnp.int32(3)                       # SingleDeviceSharding-committed
    fn = jax.jit(lambda a, i: a + i)
    fn(a, r0)
    site = led.observe("t.mixed", fn, (a, r0))
    assert site.measured_bytes_per_call == 0.0


def test_observe_before_donation_is_safe():
    led = obs.install_ledger()
    fn = jax.jit(lambda x: x + 1, donate_argnums=(0,))
    x = jnp.ones((8,), jnp.float32)
    site = led.observe("t.donated", fn, (x,))
    fn(x)                                   # x's buffer is donated here
    assert site.measured_bytes_per_call == 0.0


def test_record_analytic_site():
    led = obs.install_ledger()
    led.record("plan.x", predicted_words=10.0, lower_bound_words=5.0,
               wall_s=0.1, detail=("a",))
    led.record("plan.x", wall_s=0.2, detail=("a",))
    site = led.site("plan.x")
    assert site.calls == 2 and site.wall_s == pytest.approx(0.3)
    assert site.measured_bytes_per_call is None
    assert site.bound_fraction is None and site.drift is None


def test_audit_conventions():
    led = obs.install_ledger()
    args = (np.zeros((2, 2), np.float32),)
    # measured 512 B = 128 words over a zero floor / zero prediction
    s = led.observe("inf.case", _FakeFn(_AR_512), args)
    assert s.measured_bytes_per_call == 512.0
    assert s.measured_words_per_call == 128.0
    assert s.bound_fraction == math.inf and s.drift == math.inf
    led.clear()
    s = led.observe("exact.case", _FakeFn(_AR_512), args,
                    predicted_words=128.0, lower_bound_words=64.0)
    assert s.drift == 0.0 and s.bound_fraction == 2.0
    assert led.total_measured_bytes() == 512.0
    assert led.total_measured_bytes("other") == 0.0


def test_itemsize_scales_words():
    led = obs.install_ledger()
    s = led.observe("f64.case", _FakeFn(_AR_512),
                    (np.zeros(1, np.float64),), itemsize=8)
    assert s.measured_words_per_call == 64.0


# ---------------------------------------------------------------------------
# report: honesty table, drift flags, autotune revalidation
# ---------------------------------------------------------------------------

def test_honesty_report_renders():
    led = obs.install_ledger()
    led.observe("site.a", _FakeFn(_AR_512), (np.zeros(1),),
                predicted_words=100.0, lower_bound_words=64.0, wall_s=0.5)
    led.record("site.b", predicted_words=7.0)
    txt = obs.honesty_report(led)
    lines = txt.splitlines()
    assert lines[0].split() == ["site", "calls", "pred_words", "meas_words",
                                "thm_floor", "bound_frac", "drift", "wall_s"]
    assert "site.a" in txt and "site.b" in txt
    assert "128" in txt                     # measured words rendered
    # analytic-only site renders '-' for the measured columns
    brow = next(ln for ln in lines if ln.startswith("site.b"))
    assert "-" in brow
    # roofline column: 128 words/call at 256 words/s over 0.5 s wall = 1.0
    txt2 = obs.honesty_report(led, machine_words_per_s=256.0)
    assert "roofline_frac" in txt2.splitlines()[0]
    arow = next(ln for ln in txt2.splitlines() if ln.startswith("site.a"))
    assert arow.rstrip().endswith("1")


@settings(max_examples=40, deadline=None)
@given(mult=st.floats(min_value=0.05, max_value=20.0),
       threshold=st.floats(min_value=0.0, max_value=3.0))
def test_drift_flag_predicate_property(mult, threshold):
    """A site flags iff |measured - predicted| / predicted > threshold."""
    led = obs_ledger.CommLedger()
    measured = 128.0                        # words (512 B / itemsize 4)
    pred = measured * mult
    led.observe("s", _FakeFn(_AR_512), (np.zeros(1),),
                predicted_words=pred)
    drift = (measured - pred) / pred
    flags = obs.drift_flags(led, threshold=threshold)
    assert bool(flags) == (abs(drift) > threshold)
    if flags:
        assert flags[0][1] == pytest.approx(drift)


def test_drift_flags_sorted_and_validated():
    led = obs_ledger.CommLedger()
    led.observe("small", _FakeFn(_AR_512), (np.zeros(1),),
                predicted_words=100.0)      # drift +0.28
    led.observe("big", _FakeFn(_AR_512), (np.zeros(2),),
                predicted_words=32.0)       # drift +3.0
    led.record("analytic", predicted_words=1.0)   # never flags
    flags = obs.drift_flags(led, threshold=0.25)
    assert [s.name for s, _ in flags] == ["big", "small"]
    with pytest.raises(ValueError):
        obs.drift_flags(led, threshold=-0.1)


def test_revalidate_autotune_pops_drifted_entries(tmp_path):
    from repro.plan.autotune import AutotuneCache
    cache = AutotuneCache(str(tmp_path / "tune.json"))
    cache.put("k/drifted", {"variant": "v"})
    cache.put("k/fine", {"variant": "v"})
    led = obs_ledger.CommLedger()
    led.observe("s1", _FakeFn(_AR_512), (np.zeros(1),),
                predicted_words=32.0, cache_key="k/drifted")
    led.observe("s2", _FakeFn(_AR_512), (np.zeros(2),),
                predicted_words=128.0, cache_key="k/fine")   # drift 0
    popped = obs.revalidate_autotune(led, cache, threshold=0.25)
    assert popped == ["k/drifted"]
    assert cache.get("k/drifted") is None
    assert cache.get("k/fine") is not None
    # idempotent: already-popped keys return nothing the second time
    assert obs.revalidate_autotune(led, cache, threshold=0.25) == []


def test_plan_execute_records_analytic_site():
    from repro.plan import plan_sketch
    from repro.plan.autotune import cache_key
    led = obs.install_ledger()
    plan = plan_sketch(32, 16, 8, P=1)
    out = plan.execute(np.ones((32, 16), np.float32))
    assert out.shape == (32, 8)
    site = next(s for s in led.sites() if s.name.startswith("plan.execute["))
    assert site.calls == 1 and site.wall_s > 0
    assert site.cache_key == cache_key(plan)
    assert site.measured_bytes_per_call is None   # analytic-only


# ---------------------------------------------------------------------------
# HLO classification: collective-permute / all-to-all (roofline/hlo.py)
# ---------------------------------------------------------------------------

def test_hlo_collective_permute_moving():
    cb = collective_bytes_of("""
HloModule m, num_partitions=4
%p0 = f32[16,8]{1,0} parameter(0)
%cp = f32[16,8]{1,0} collective-permute(%p0), \
source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
""")
    assert cb.by_kind == {"collective-permute": 512.0}
    assert cb.counts == {"collective-permute": 1}
    assert cb.permute_pairs == 4 and cb.permute_identity_pairs == 0
    assert cb.redistribute_total == 512.0 and cb.total == 512.0
    assert cb.num_partitions == 4


def test_hlo_collective_permute_identity_only_is_noop():
    cb = collective_bytes_of("""
HloModule m
%p0 = f32[16,8]{1,0} parameter(0)
%cp = f32[16,8]{1,0} collective-permute(%p0), \
source_target_pairs={{0,0},{1,1}}
""")
    assert cb.total == 0.0 and cb.counts == {}
    assert cb.permute_pairs == 0 and cb.permute_identity_pairs == 0


def test_hlo_collective_permute_mixed_pairs_counted():
    cb = collective_bytes_of("""
HloModule m
%p0 = f32[16,8]{1,0} parameter(0)
%cp = f32[16,8]{1,0} collective-permute(%p0), \
source_target_pairs={{0,0},{1,2},{2,1},{3,3}}
""")
    assert cb.by_kind == {"collective-permute": 512.0}
    assert cb.permute_pairs == 2 and cb.permute_identity_pairs == 2


def test_hlo_collective_permute_async_start_form():
    cb = collective_bytes_of("""
HloModule m
%p0 = f32[16,8]{1,0} parameter(0)
%cps = (f32[16,8]{1,0}, f32[16,8]{1,0}) collective-permute-start(%p0), \
source_target_pairs={{0,1},{1,0}}
%cpd = f32[16,8]{1,0} collective-permute-done(%cps)
""")
    # -start counted once via its operand; -done contributes nothing
    assert cb.by_kind == {"collective-permute": 512.0}
    assert cb.counts == {"collective-permute": 1}
    assert cb.permute_pairs == 2


def test_hlo_all_to_all_bytes_and_group_size_one():
    cb = collective_bytes_of("""
HloModule m
%p0 = f32[32,4]{1,0} parameter(0)
%a2a = f32[32,4]{1,0} all-to-all(%p0), replica_groups={{0,1,2,3}}, \
dimensions={0}
%deg = f32[32,4]{1,0} all-to-all(%p0), replica_groups={{0}}, \
dimensions={0}
""")
    assert cb.by_kind == {"all-to-all": 512.0}      # degenerate one skipped
    assert cb.counts == {"all-to-all": 1}
    assert cb.redistribute_total == 512.0


def test_hlo_redistribute_total_excludes_reductions():
    cb = collective_bytes_of("""
HloModule m
%p0 = f32[16,8]{1,0} parameter(0)
%ar = f32[16,8]{1,0} all-reduce(%p0), replica_groups={{0,1}}
%cp = f32[16,8]{1,0} collective-permute(%p0), \
source_target_pairs={{0,1},{1,0}}
""")
    assert cb.total == 1024.0
    assert cb.redistribute_total == 512.0


def test_hlo_unresolvable_operand_falls_back_to_result_shape():
    cb = collective_bytes_of("""
HloModule m
%cp = f32[4,4]{1,0} collective-permute(%unknown), \
source_target_pairs={{0,1}}
""")
    assert cb.by_kind == {"collective-permute": 64.0}


# ---------------------------------------------------------------------------
# ingest stats hardening (satellite: percentile math + reset semantics)
# ---------------------------------------------------------------------------

def test_percentile_guards():
    from repro.stream.ingest import _percentile
    assert _percentile([], 50) == 0.0
    assert _percentile(None, 99) == 0.0
    assert _percentile([float("nan"), float("inf")], 50) == 0.0
    assert _percentile([0.25], 99) == 0.25
    xs = [0.1, 0.2, 0.3, 0.4]
    assert _percentile(xs, 50) == pytest.approx(np.percentile(xs, 50))
    # non-finite entries are dropped, not propagated
    assert _percentile([0.5, float("nan")], 50) == 0.5


def _local_service_and_queue(n_streams=2, n1=32, n2=16, r=4):
    from repro.serve.engine import make_ingest_queue, make_sketch_service
    from repro.stream.state import StreamConfig
    svc = make_sketch_service()
    sids = [svc.open(StreamConfig(n1=n1, n2=n2, r=r, seed=s))
            for s in range(n_streams)]
    return svc, sids, make_ingest_queue(svc, depth=16, window=8)


def test_stats_reset_clears_window_not_lifetime():
    svc, sids, q = _local_service_and_queue()
    with q:
        for sid in sids:
            q.submit(sid, np.ones((4, 16), np.float32), 0)
        q.flush(raise_errors=True)
        st1 = q.stats(reset=True)
        assert st1["submitted"] == 2 and st1["applied"] == 2
        assert st1["latency_p99_s"] > 0.0
        assert st1["real_rows"] == 8
        st2 = q.stats()
        # window figures cleared...
        assert st2["latency_p50_s"] == 0.0 and st2["latency_p99_s"] == 0.0
        assert st2["real_rows"] == 0 and st2["padded_rows"] == 0
        assert st2["pad_waste"] == 0.0
        # ...lifetime counters preserved
        assert st2["submitted"] == 2 and st2["applied"] == 2
        assert st2["rounds"] == st1["rounds"]


# ---------------------------------------------------------------------------
# serving metrics + cross-thread parenting through the ingest queue
# ---------------------------------------------------------------------------

def test_service_and_queue_publish_metrics():
    with fresh_metrics() as reg:
        svc, sids, q = _local_service_and_queue(n_streams=3)
        with q:
            svc.update(sids[0], np.ones((32, 16), np.float32))
            for sid in sids:
                q.submit(sid, np.ones((5, 16), np.float32), 0)
            q.flush(raise_errors=True)
        upd = reg.counter("sketch_updates_total")
        assert upd.value(path="single") == 1
        assert upd.value(path="ragged") == 3
        assert reg.counter("ingest_submitted_total").value() == 3
        assert reg.counter("ingest_applied_total").value() == 3
        assert reg.gauge("sketch_resident_streams").value() == 3
        assert reg.histogram("ingest_drain_latency_seconds").count() >= 1
        assert reg.counter("sketch_ragged_real_rows_total").value() == 15
        text = reg.prometheus_text()
        assert 'sketch_updates_total{path="ragged"} 3' in text
        assert "ingest_drain_latency_seconds_count" in text


def test_service_eviction_metrics():
    from repro.stream.service import SketchService
    from repro.stream.state import StreamConfig
    with fresh_metrics() as reg:
        svc = SketchService(max_resident=1)
        a = svc.open(StreamConfig(n1=16, n2=16, r=4, seed=0))
        svc.update(a, np.ones((16, 16), np.float32))
        b = svc.open(StreamConfig(n1=16, n2=16, r=4, seed=1))  # evicts a
        svc.update(a, np.ones((16, 16), np.float32))           # restores a
        del b
        assert reg.counter("sketch_evictions_total").value() >= 1
        assert reg.counter("sketch_restores_total").value() >= 1
        assert reg.gauge("sketch_resident_streams").value() == 1


def test_ingest_spans_parent_across_threads():
    tracer = obs.install_tracer()
    svc, sids, q = _local_service_and_queue(n_streams=1)
    with q:
        q.hold()
        with obs_trace.span("client.request", cat="test"):
            q.submit(sids[0], np.ones((4, 16), np.float32), 0)
            submit_parent = None  # captured by the queue, not by us
        q.release()
        q.flush(raise_errors=True)
    del submit_parent
    names = {}
    for s in tracer.spans:
        names.setdefault(s.name, s)
    client = names["client.request"]
    apply_ = names["ingest.apply_round"]
    assert apply_.parent_id == client.span_id
    assert apply_.tid != client.tid         # stitched across the worker


# ---------------------------------------------------------------------------
# recovery observability (ISSUE 9): WAL / replay / reshard / retry signals
# ---------------------------------------------------------------------------

def test_recovery_metrics_and_spans(tmp_path):
    """Every fault-tolerance path leaves an audit trail: the WAL depth
    gauge drains back to 0, replay/reshard/retry count, and the recovery
    arcs open named spans."""
    from repro.core.sketch import make_grid_mesh
    from repro.stream import faults
    from repro.stream import wal as wal_mod
    from repro.stream.elastic import drain_reshard_resume
    from repro.stream.ingest import IngestQueue
    from repro.stream.service import SketchService
    from repro.stream.state import StreamConfig

    tracer = obs.install_tracer()
    cfg = StreamConfig(n1=32, n2=16, r=4, seed=0, corange=False)
    try:
        with fresh_metrics() as reg:
            # journaled ingest: the depth gauge returns to 0 once applied
            svc = SketchService()
            sid = svc.open(cfg)
            wal = wal_mod.WriteAheadLog(str(tmp_path / "ingest.wal"))
            with IngestQueue(svc, wal=wal) as q:
                q.submit(sid, np.ones((4, 16), np.float32), 0)
                q.flush(raise_errors=True)
            wal.close()
            assert reg.gauge("stream_wal_depth").value() == 0

            # replay counts each re-applied record
            svc2 = SketchService()
            sid2 = svc2.open(cfg)
            n, _ = wal_mod.replay(wal.path, svc2, sid_map={sid: sid2})
            assert n == 1
            assert reg.counter("stream_replays_total").value() == 1

            # a transient round failure counts one retry
            faults.arm("ingest.apply_round", exc=faults.FaultInjected,
                       times=1)
            with IngestQueue(svc, max_retries=1, backoff_base=0.0) as q2:
                q2.submit(sid, np.ones((4, 16), np.float32), 0)
                q2.flush(raise_errors=True)
            faults.clear()
            assert reg.counter("ingest_retries_total").value() == 1

            # drain -> reshard -> resume counts one hop per stream
            dsvc = SketchService(mesh=make_grid_mesh(1, 1, 1))
            dsid = dsvc.open(cfg)
            with IngestQueue(dsvc) as q3:
                q3.submit(dsid, np.ones((32, 16), np.float32))
                out = drain_reshard_resume(q3, (1, 1, 1))
            assert out["resharded"] == 1
            assert reg.counter("stream_reshard_total").value() == 1

            text = reg.prometheus_text()
            for name in ("stream_wal_depth", "stream_replays_total",
                         "stream_reshard_total", "ingest_retries_total",
                         "ingest_quarantined_total"):
                assert name in text, name
    finally:
        faults.clear()

    names = {s.name for s in tracer.spans}
    assert {"stream.wal_replay", "stream.reshard",
            "stream.drain_reshard_resume"} <= names
    resh = next(s for s in tracer.spans if s.name == "stream.reshard")
    assert resh.args["old"] == "1x1x1" and resh.args["new"] == "1x1x1"
    assert resh.args["path"] == "jit"    # same device set -> measurable


# ---------------------------------------------------------------------------
# overhead budget: tracer + ledger on the jitted ragged-update hot path
# ---------------------------------------------------------------------------

def test_traced_update_ragged_overhead_under_2pct():
    from repro.stream.service import SketchService
    from repro.stream.state import StreamConfig
    svc = SketchService()
    sids = [svc.open(StreamConfig(n1=256, n2=128, r=8, seed=s,
                                  corange=False))
            for s in range(16)]
    items = [(sid, np.ones((64, 128), np.float32), 0) for sid in sids]

    def one_round():
        svc.update_ragged(items)
        svc.sync()

    one_round()                             # compile + warm every path

    def timed():
        t0 = time.perf_counter()
        one_round()
        return time.perf_counter() - t0

    # INTERLEAVED pairs: an untraced and a traced round back to back per
    # rep, so both classes sample the same noise environment (separate
    # min-of-N blocks make the min estimator compare different warming /
    # scheduling regimes and swamp a percent-level budget).  The tracer
    # and ledger are REUSED across pairs and warmed once: the budget is a
    # steady-state property (install once, run many rounds) — a fresh
    # ledger per pair would bill every traced round as a first call at
    # its signature (abstractify + site registration, ~50us) and measure
    # install churn, not the hot path.  The budget must hold for SOME
    # attempt, not on the first try.
    tracer = obs.Tracer(max_spans=1_000_000)
    ledger = obs.CommLedger()
    obs.install_tracer(tracer)
    obs.install_ledger(ledger)
    one_round()                             # warm first-observe machinery
    obs.uninstall_observability()
    for attempt in range(6):
        untraced = traced = math.inf
        for _ in range(40):
            untraced = min(untraced, timed())
            obs.install_tracer(tracer)
            obs.install_ledger(ledger)
            try:
                traced = min(traced, timed())
            finally:
                obs.uninstall_observability()
        if traced <= 1.02 * untraced:
            break
    else:
        pytest.fail(f"traced/untraced = {traced / untraced:.4f} > 1.02 "
                    f"after {attempt + 1} attempts")


# ---------------------------------------------------------------------------
# the acceptance audit: ledger bytes == direct HLO audit, exactly
# ---------------------------------------------------------------------------

def test_ledger_matches_hlo_audits_distributed():
    run_distributed("""
import numpy as np, jax, jax.numpy as jnp
from repro import obs
from repro.core.sketch import make_grid_mesh
from repro.roofline.hlo import collective_bytes_of
from repro.stream.state import StreamConfig
from repro.stream.distributed import ShardedStreamingSketch, input_sharding

tracer, ledger, _ = obs.install_observability()

# --- Alg. 1 (P,1,1) = (8,1,1): the zero-communication regime ---
mesh = make_grid_mesh(8, 1, 1)
st = ShardedStreamingSketch(StreamConfig(n1=16, n2=32, r=8, seed=3,
                                         corange=False), mesh)
st.update(jnp.ones((16, 32), jnp.float32))
s = ledger.site("stream.update")
assert s.measured_bytes_per_call == 0.0, s
assert s.drift == 0.0 and s.bound_fraction == 1.0, s
assert ledger.total_measured_bytes() == 0.0
assert len(tracer.spans) >= 1
ledger.clear()
print("OK 811")

# --- (2,2,2): ledger == direct parse of the SAME executable ---
mesh2 = make_grid_mesh(2, 2, 2)
cfg_no = StreamConfig(n1=16, n2=64, r=8, seed=3, corange=False)
cfg_co = StreamConfig(n1=16, n2=64, r=8, seed=3, corange=True)
H = jnp.ones((16, 64), jnp.float32)
meas = {}
for tag, cfg in (("no", cfg_no), ("co", cfg_co)):
    st2 = ShardedStreamingSketch(cfg, mesh2)
    st2.update(H)
    st2.update(H)
    site = ledger.site("stream.update")
    Hd = jax.device_put(H, input_sharding(mesh2, st2.axes))
    direct = collective_bytes_of(
        st2._upd.lower(st2.Y, st2.W, Hd).compile().as_text())
    assert site.calls == 2, site
    assert site.measured_bytes_per_call == direct.total, (site, direct)
    assert site.measured_bytes == 2 * direct.total
    meas[tag] = site.measured_bytes_per_call
    ledger.clear()
# corange delta: the Psi-partial psum moves exactly l * n2/(p2 p3) words
assert meas["co"] - meas["no"] == cfg_co.sketch_l * (64 // 4) * 4, meas
print("OK 222 update")

# --- row-slab ingest: the slab cost model is exact on this grid ---
st2 = ShardedStreamingSketch(cfg_co, mesh2)
st2.update_rows(0, jnp.ones((4, 64), jnp.float32))
s3 = ledger.site("stream.update_rows")
assert s3.measured_bytes_per_call is not None
assert s3.drift == 0.0, s3          # measured == stream_update_cost words
ledger.clear()
print("OK 222 rows")

# --- service dist path on (8,1,1): zero bytes at the bound ---
from repro.stream.service import SketchService
svc = SketchService(mesh=mesh)
sid = svc.open(StreamConfig(n1=64, n2=64, r=16, seed=5, corange=False))
svc.update(sid, np.ones((64, 64), np.float32))
s4 = ledger.site("service.update[dist]")
assert s4.measured_bytes_per_call == 0.0, s4
assert s4.drift == 0.0 and s4.bound_fraction == 1.0, s4
ledger.clear()
print("OK service dist")

# --- fused two-grid regime-1 pair p=(8,1,1), q=(1,1,8): the in-program
# Redistribute is the ONLY traffic and carries exactly nr/P per device ---
from repro.core.nystrom import nystrom_two_grid_fused
n, r = 64, 16
rng = np.random.default_rng(0)
G = rng.standard_normal((n, n)).astype(np.float32)
S = jnp.asarray(G @ G.T)
nystrom_two_grid_fused(S, 7, r, p=(8, 1, 1), q=(1, 1, 8))
s5 = ledger.site("nystrom.two_grid_fused")
assert s5.measured_bytes_per_call == n * r / 8 * 4, s5
cb = s5.collectives()
assert cb.redistribute_total == cb.total, cb
print("OK fused pair")

# honesty report renders all of it without error
print(obs.honesty_report(ledger))
""", timeout=900)
