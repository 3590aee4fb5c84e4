"""The program's spans and counters (repro.obs) and where the served path
records them: admission spans, compile records, serve() phases, and the
named scopes that label device ops."""

import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs import ShapeConfig, get_config
from repro.launch.mesh import make_mesh
from repro.launch.serve import BatchAdmission, _next_token, serve
from repro.launch.steps import build_decode_step
from repro.models import Model


def test_ring_is_bounded_and_keeps_order():
    obs.reset()
    extra = 10
    for i in range(obs.RING_SIZE + extra):
        obs.record("t.ring", i, i + 1, i=i)
    kept = obs.spans("t.ring")
    assert len(kept) == obs.RING_SIZE
    assert [s.attrs["i"] for s in kept[:3]] == [extra, extra + 1, extra + 2]
    assert all(a.start_ns < b.start_ns for a, b in zip(kept, kept[1:]))
    obs.reset()
    assert obs.spans() == []


def test_attributes_survive_and_filters_apply():
    obs.reset()
    t = time.perf_counter_ns()
    with obs.span("t.outer", batch=3) as s:
        s.attrs["ops"] = 7
        with obs.span("t.inner"):
            pass
    obs.record("u.late", s.end_ns + 10, s.end_ns + 20, flag=True)
    outer, = obs.spans("t.outer")
    assert outer.attrs == {"batch": 3, "ops": 7}
    assert t <= outer.start_ns < outer.end_ns and outer.seconds > 0
    assert [x.name for x in obs.spans("t.")] == ["t.inner", "t.outer"]
    assert [x.name for x in obs.spans(since_ns=s.end_ns)] == ["u.late"]
    assert [x.name for x in obs.spans(until_ns=s.end_ns)] == ["t.inner", "t.outer"]
    assert obs.spans("u.")[0].attrs == {"flag": True}


def test_span_records_when_the_body_raises():
    obs.reset()
    with pytest.raises(ValueError):
        with obs.span("t.fails"):
            raise ValueError("boom")
    assert len(obs.spans("t.fails")) == 1


def test_span_lands_on_the_profiler_timeline_while_tracing(tmp_path):
    from jax.profiler import ProfileData

    obs.reset()
    with obs.span("t.untraced"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("t.traced", batch=1):
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    path, = tmp_path.glob("**/*.xplane.pb")
    names = {e.name for p in ProfileData.from_file(str(path)).planes
             if p.name.startswith("/host:")
             for line in p.lines for e in line.events}
    assert "t.traced" in names and "t.untraced" not in names
    assert [s.name for s in obs.spans("t.")] == ["t.untraced", "t.traced"]
    assert obs.spans("t.traced")[0].seconds >= 0.001


def test_compile_listener_records_fresh_compiles_only():
    f = jax.jit(lambda x: jnp.tanh(x) * 3 + 1)
    x = jnp.ones((7, 13), jnp.float32)
    t = time.perf_counter_ns()
    f(x).block_until_ready()
    first = obs.spans(obs.COMPILE_SPAN, since_ns=t)
    assert len(first) >= 1
    assert any("lambda" in str(s.attrs["fun_name"]) for s in first)
    assert all(t <= s.start_ns <= s.end_ns for s in first)
    t2 = time.perf_counter_ns()
    f(x).block_until_ready()
    assert obs.spans(obs.COMPILE_SPAN, since_ns=t2) == []


def _run_batches(admission, n, keepalives):
    t = time.perf_counter_ns()
    for _ in range(n):
        lease = admission.admit(timeout=5.0)
        for _ in range(keepalives):
            lease = admission.keepalive(lease)
        assert admission.complete(lease)
    return obs.spans("admission.", since_ns=t)


def test_admission_spans_cost_no_rdma_on_the_serving_host():
    """The default one-host table: every admission span pays 0 RDMA ops and
    each batch costs the same local lock-table ops (the paper's local
    path, seen on the served path)."""
    adm = BatchAdmission(num_slots=2)
    calls = _run_batches(adm, n=5, keepalives=3)
    assert [s.name for s in calls[:5]] == (
        ["admission.admit"] + ["admission.keepalive"] * 3
        + ["admission.complete"])
    assert all(s.attrs["rdma_ops"] == 0 for s in calls)
    assert all(s.attrs["local_ops"] > 0 for s in calls)
    per_batch = {}
    for s in calls:
        key = (s.attrs["key"], s.attrs["token"])
        per_batch[key] = per_batch.get(key, 0) + s.attrs["local_ops"]
    assert len(per_batch) == 5 and len(set(per_batch.values())) == 1
    stats = adm.stats()
    assert stats["local_rdma_ops"] == 0
    # The spans' counters are the numbers stats() aggregates: every table
    # operation of the admission happens inside one of its spans.
    assert sum(per_batch.values()) == stats["local_ops"]


def test_admission_spans_count_the_calling_threads_ops():
    """Two server threads admit at once: each span carries only its own
    thread's operations, and together they account for all of them."""
    adm = BatchAdmission(num_slots=2)
    t = time.perf_counter_ns()
    done = []

    def worker():
        for _ in range(20):
            lease = adm.admit(timeout=5.0)
            lease = adm.keepalive(lease)
            done.append(adm.complete(lease))

    threads = [threading.Thread(target=worker) for _ in range(2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=60)
    assert len(done) == 40 and all(done)
    calls = obs.spans("admission.", since_ns=t)
    assert len(calls) == 40 * 3
    assert sum(s.attrs["local_ops"] for s in calls) == adm.stats()["local_ops"]
    assert all(s.attrs["rdma_ops"] == 0 for s in calls)


def test_start_and_end_write_one_span(tmp_path):
    """The lean pair the admission path uses: one ring entry with the
    attributes given to end, and the profiler annotation while tracing."""
    from jax.profiler import ProfileData

    opened = obs.start("t.lean")
    end_ns = obs.end(opened, key="k", ops=3)
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = obs.start("t.lean.traced")
        obs.end(traced)
    finally:
        jax.profiler.stop_trace()
    first, second = obs.spans("t.lean", since_ns=opened[2])
    assert first.attrs == {"key": "k", "ops": 3}
    assert first.end_ns == end_ns and first.start_ns <= end_ns
    assert second.name == "t.lean.traced" and second.attrs == {}
    path, = tmp_path.glob("**/*.xplane.pb")
    names = {e.name for p in ProfileData.from_file(str(path)).planes
             if p.name.startswith("/host:")
             for line in p.lines for e in line.events}
    assert "t.lean.traced" in names and "t.lean" not in names


def test_clock_pair_maps_ring_times_onto_the_wall_clock():
    wall, perf = time.time_ns(), time.perf_counter_ns()
    mapped = obs.CLOCK_PAIR[0] + perf - obs.CLOCK_PAIR[1]
    assert abs(mapped - wall) < 50_000_000  # the two clocks drift apart slowly


def test_serve_records_its_phases_as_spans():
    gen = 10
    t = time.perf_counter_ns()
    out = serve("llama3.2-1b", smoke=True, batch=2, prompt_len=16, gen_len=gen,
                admission=BatchAdmission(num_slots=1))
    names = [s.name for s in obs.spans("serve.", since_ns=t)]
    assert names.count("serve.compile") == 1
    assert names.count("serve.prefill") == 1
    assert names.count("serve.decode") == gen - 1
    assert names.count("serve.sample") == gen
    assert names.count("serve.pull") == gen
    compile_span, = obs.spans("serve.compile", since_ns=t)
    prefill_span, = obs.spans("serve.prefill", since_ns=t)
    assert out["compile_seconds"] == compile_span.seconds
    assert out["prefill_seconds"] == prefill_span.seconds
    decodes = obs.spans("serve.decode", since_ns=t)
    pulls = obs.spans("serve.pull", since_ns=t)
    assert out["decode_seconds_per_token"] == pytest.approx(
        (pulls[-1].end_ns - decodes[0].start_ns) * 1e-9 / (gen - 1))
    # Eight decode steps per keepalive, one after the prefill.
    assert len(obs.spans("admission.keepalive", since_ns=t)) == 1 + (gen - 1) // 8


def test_named_scopes_label_device_ops():
    """The decode step at the smoke config carries the block's parts, the
    head and the sampler in its ops' metadata."""
    cfg = get_config("glm4-9b", smoke=True)
    model = Model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    max_len = 24
    with jax.set_mesh(mesh):
        decode_fn, cache_spec, _ = build_decode_step(
            model, mesh, ShapeConfig("t", max_len, 2, "decode"), max_len)
        exe = decode_fn.lower(model.param_shapes(), cache_spec,
                              jax.ShapeDtypeStruct((2, 1), jnp.int32)).compile()
        text = exe.as_text()
        sample = _next_token.lower(exe.out_info[0], jax.random.PRNGKey(0), 1,
                                   True).compile().as_text()
    for scope in ("attn", "ffn", "head"):
        assert f"/{scope}/" in text, scope
    assert "/sample/" in sample


def test_failed_admission_records_a_span_without_a_lease():
    adm = BatchAdmission(num_slots=1)
    held = adm.admit(timeout=5.0)
    t = time.perf_counter_ns()
    with pytest.raises(TimeoutError):
        adm.admit(timeout=0.01)
    failed, = obs.spans("admission.admit", since_ns=t)
    assert failed.attrs["key"] is None and failed.attrs["token"] is None
    assert failed.attrs["local_ops"] > 0 and failed.attrs["rdma_ops"] == 0
    assert adm.complete(held)
