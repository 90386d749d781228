"""Sharded + disaggregated serving acceptance: the ``deployment`` grid's
greedy token streams are BIT-IDENTICAL to the single-device per-request
``Engine.generate`` oracle.

The sharded tests need multiple host devices; CI's sharded-smoke job sets
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the flag must be
in the ENVIRONMENT before jax imports — tests never set it in-process, so
a plain tier-1 run simply skips the >1-device cells of the grid and still
exercises the shard_map lowering on the degenerate (1, 1) mesh). Sub-mesh
cells build their mesh over ``jax.devices()[:n]``, so 1-, 2- and 4-device
topologies all run inside one forced-4-device process.

What the grid pins, per (devices, tick_mode, speculate_k) cell:

* every request's greedy stream equals the Engine oracle's, under a
  schedule tight enough to force preemption + swap on the sharded pool;
* packed mode still dispatches ONE compiled shape (speculation off) —
  sharding must not fracture the single-(1, T)-buffer property;
* the pool drains to zero pages (leak check).

Plus: the kv-pool randomized invariant walk re-run over a mesh-sharded
pool (same host allocator, device leaves placed by NamedSharding), and
the disaggregated prefill→decode deployment held to the same oracle with
page-stream transport accounting checked end to end.
"""

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_serving_mesh
from repro.models.transformer import RuntimeOpts, init_params
from repro.serving.engine import Engine
from repro.serving.page_transport import DisaggregatedScheduler
from repro.serving.scheduler import Scheduler
from repro.serving.telemetry import Tracer

OPTS_Q = RuntimeOpts(q_chunk=16, kv_chunk=16, remat=False, quantized_kv=True,
                     moe_capacity_factor=0.0)
GRID = [(n, mode, k) for n in (1, 2, 4)
        for mode in ("packed", "chunked", "wave") for k in (0, 2)]


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("llama2-7b").tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def oracle(tiny_model):
    """Per-request greedy Engine reference, memoized across the grid."""
    cfg, params = tiny_model
    eng = Engine(cfg, params, OPTS_Q, cache_len=64)
    cache = {}

    def get(prompt, max_new):
        key = (prompt.tobytes(), len(prompt), max_new)
        if key not in cache:
            cache[key] = eng.generate(prompt[None], max_new).tokens[0]
        return cache[key]

    return get


def _mesh_or_skip(cfg, n):
    if jax.device_count() < n:
        pytest.skip(f"needs {n} host devices — run under "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=4")
    return make_serving_mesh(cfg.pattern[0].mixer.num_kv_heads,
                             devices=jax.devices()[:n])


def _workload(cfg, seed=0, n_jobs=4):
    """A fixed mixed workload: staggered submits, repetitive prompts (so
    prompt-lookup drafts get accepted) and random ones, sized against a
    24-page pool with 3 slots so preemption + swap fire mid-run."""
    rng = np.random.default_rng(seed)
    jobs = []
    for i in range(n_jobs):
        if i % 2:
            base = rng.integers(0, cfg.vocab_size, (3,))
            prompt = np.tile(base, 4)[: int(rng.integers(5, 11))]
        else:
            prompt = rng.integers(0, cfg.vocab_size, (int(rng.integers(3, 13)),))
        jobs.append((prompt.astype(np.int32), int(rng.integers(2, 7)),
                     int(rng.integers(0, 3))))  # (prompt, max_new, submit_at)
    return jobs


def _drive(sched, jobs):
    rids, tick = {}, 0
    while True:
        for j, (prompt, max_new, submit_at) in enumerate(jobs):
            if j not in rids and submit_at <= tick:
                rids[j] = sched.submit(prompt, max_new)
        if sched.pending:
            sched.step()
        elif len(rids) == len(jobs):
            break
        tick += 1
        assert tick < 400, "schedule failed to drain"
    return rids


def _assert_streams_match(sched, rids, jobs, oracle):
    events = sched.drain_events()
    seen = {}
    for rid, idx, tok, lp, _ in events:
        assert idx == seen.get(rid, -1) + 1, f"rid {rid} events out of order"
        seen[rid] = idx
        assert np.isfinite(lp)
    for j, (prompt, max_new, _) in enumerate(jobs):
        got = sched.results[rids[j]]
        want = oracle(prompt, max_new)
        np.testing.assert_array_equal(
            got, want, err_msg=f"job {j} diverged from the Engine oracle")


# ------------------------------------------------------- sharded scheduler


@pytest.mark.parametrize("n,mode,k", GRID,
                         ids=[f"d{n}-{m}-k{k}" for n, m, k in GRID])
def test_sharded_streams_match_engine(tiny_model, oracle, n, mode, k):
    """Acceptance: the shard_map-lowered scheduler over an n-device mesh
    emits bit-identical greedy streams to the single-device Engine, in
    every tick mode, speculation off and on, under preemption pressure."""
    cfg, params = tiny_model
    mesh = _mesh_or_skip(cfg, n)
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=24, page_size=4,
                      max_slots=3, tick_mode=mode, speculate_k=k,
                      lazy_growth=True, mesh=mesh)
    jobs = _workload(cfg, seed=7)
    rids = _drive(sched, jobs)
    _assert_streams_match(sched, rids, jobs, oracle)
    assert sched.pool.pages_in_use == 0, "sharded pool leaked pages"
    if mode == "packed":
        assert sched.stats.packed_ticks > 0
        if k == 0:
            # sharding must not fracture the one-(1, T)-buffer property
            assert sched.stats.compiled_shapes == 1
    if k:
        assert sched.stats.spec_rounds > 0


def test_sharded_pool_leaves_are_mesh_placed(tiny_model):
    """The mesh-mode pool's device leaves carry the page-axis
    NamedSharding (axis 1 split over 'kv', block tables replicated) while
    the host allocator stays byte-identical to the unsharded pool."""
    cfg, params = tiny_model
    mesh = _mesh_or_skip(cfg, 2)
    from repro.serving.kv_pool import PagedKVPool
    pool = PagedKVPool(cfg, num_pages=16, page_size=4, max_requests=3,
                       mesh=mesh)
    s = pool.admit(6)
    pool.commit_prefill(s, 6)
    caches = pool.device_caches()
    leaf = jax.tree_util.tree_leaves(caches)[0]
    spec = leaf.sharding.spec
    assert spec[1] == "kv", f"page axis not sharded over kv: {spec}"
    pool.free(s)
    assert pool.pages_in_use == 0


def test_sharded_pool_property_walk(tiny_model):
    """The kv-pool randomized ownership walk (tests/test_kv_pool.py),
    re-run with the pool's leaves sharded over a 2-device mesh — the host
    allocator invariants must be mesh-blind."""
    cfg, _ = tiny_model
    mesh = _mesh_or_skip(cfg, 2)
    from tests.test_kv_pool import _check_pool_invariants, make_pool
    from repro.serving.kv_pool import PoolExhaustedError
    rng = np.random.default_rng(99)
    pool = make_pool(num_pages=20, page_size=4, max_requests=4, mesh=mesh)
    handles: list = []
    for _ in range(80):
        op = rng.integers(0, 4)
        active = list(np.flatnonzero(pool.active))
        try:
            if op == 0:
                n = int(rng.integers(1, 13))
                s = pool.admit(n)
                pool.commit_prefill(s, n)
            elif op == 1 and active:
                pool.append(active[rng.integers(len(active))],
                            int(rng.integers(1, 4)))
            elif op == 2 and active:
                s = active[rng.integers(len(active))]
                if int(pool.lengths[s]) >= 2:
                    handles.append(pool.share_prefix(
                        s, int(rng.integers(1, int(pool.lengths[s])))))
            elif op == 3 and active:
                pool.free(active[rng.integers(len(active))])
        except PoolExhaustedError:
            pass
        _check_pool_invariants(pool, handles)
    for s in list(np.flatnonzero(pool.active)):
        pool.free(s)
    for h in handles:
        pool.release_prefix(h)
    _check_pool_invariants(pool, handles)
    assert pool.pages_in_use == 0


# ------------------------------------------------- disaggregated serving


@pytest.mark.parametrize("mode,k", [("packed", 0), ("packed", 2),
                                    ("chunked", 2), ("wave", 0)],
                         ids=["packed-k0", "packed-k2", "chunked-k2",
                              "wave-k0"])
def test_disaggregated_streams_match_engine(tiny_model, oracle, mode, k):
    """Acceptance: prefill→decode disaggregation (two pools + the page
    stream) emits bit-identical greedy streams, events stay in per-request
    index order across the replica handoff, both pools drain, and every
    transferred byte lands in the transport spans/metrics."""
    cfg, params = tiny_model
    tr = Tracer()
    ds = DisaggregatedScheduler(cfg, params, OPTS_Q, telemetry=tr,
                                num_pages=24, page_size=4, max_slots=3,
                                tick_mode=mode, lazy_growth=True,
                                decode_kwargs={"speculate_k": k})
    jobs = _workload(cfg, seed=11, n_jobs=5)
    rids = _drive(ds, jobs)
    _assert_streams_match(ds, rids, jobs, oracle)
    assert ds.prefill.pool.pages_in_use == 0
    assert ds.decode.pool.pages_in_use == 0
    # multi-token requests crossed the stream; their bytes are accounted
    multi = sum(1 for _, max_new, _ in jobs if max_new > 1)
    assert ds.transport.transfers == multi * len(cfg.pattern)
    assert ds.transport.bytes_moved > 0
    spans = [sp for sp in tr.spans if sp.name == "page_stream"]
    assert sum(sp.attrs["bytes"] for sp in spans) == ds.transport.bytes_moved
    m = tr.metrics_dict()
    assert m["transport.page_stream.total_bytes"] == ds.transport.bytes_moved
    # swap-byte ownership handed off cleanly: neither pool holds residue
    assert ds.prefill.pool.swap_bytes == 0
    assert ds.decode.pool.swap_bytes == 0
    # ttft is a prefill-replica quantity; the merged stats carry it
    assert set(rids.values()) <= set(ds.stats.ttft_ticks)


def test_disaggregated_single_token_requests_finish_on_prefill(tiny_model,
                                                               oracle):
    """max_new_tokens == 1 finishes on the prefill replica — nothing to
    decode, nothing crosses the stream."""
    cfg, params = tiny_model
    ds = DisaggregatedScheduler(cfg, params, OPTS_Q, num_pages=24,
                                page_size=4, max_slots=3, tick_mode="packed",
                                lazy_growth=True)
    prompt = np.arange(1, 7, dtype=np.int32)
    rid = ds.submit(prompt, 1)
    res = ds.run()
    np.testing.assert_array_equal(res[rid], oracle(prompt, 1))
    assert ds.transport.transfers == 0
    assert rid in ds.prefill.results and rid not in ds.decode.results


def test_disaggregated_mismatched_page_size_rejected(tiny_model):
    cfg, params = tiny_model
    with pytest.raises(ValueError, match="page_size"):
        DisaggregatedScheduler(cfg, params, OPTS_Q, num_pages=16,
                               page_size=4, max_slots=2,
                               decode_kwargs={"page_size": 8})


# ------------------------------------------------------- deployment knob


def test_server_deployment_knob(tiny_model):
    """The ``deployment=`` knob on the paged backend: 'disaggregated'
    serves through the facade, 'fused' rejects a mesh, unknown names
    raise. (The 'sharded' path is covered device-parametrized above —
    here just the 1-device degenerate mesh build.)"""
    from repro.serving.api import LLMServer, SamplingParams

    cfg, params = tiny_model
    srv = LLMServer(cfg, params, OPTS_Q, backend="paged",
                    deployment="disaggregated", num_pages=24, page_size=4,
                    max_slots=3, tick_mode="packed", lazy_growth=True)
    prompt = np.arange(2, 9, dtype=np.int32)
    rid = srv.submit(prompt, SamplingParams(max_tokens=4))
    out = srv.run()[rid]
    eng = Engine(cfg, params, OPTS_Q, cache_len=64)
    want = eng.generate(prompt[None], 4).tokens[0][len(prompt):]
    np.testing.assert_array_equal(out.tokens, want)

    srv2 = LLMServer(cfg, params, OPTS_Q, backend="paged",
                     deployment="sharded", num_pages=24, page_size=4,
                     max_slots=3, lazy_growth=True)
    assert srv2.backend.scheduler.mesh is not None
    rid2 = srv2.submit(prompt, SamplingParams(max_tokens=4))
    out2 = srv2.run()[rid2]
    np.testing.assert_array_equal(out2.tokens, want)

    with pytest.raises(ValueError, match="deployment='sharded'"):
        LLMServer(cfg, params, OPTS_Q, backend="paged",
                  mesh=make_serving_mesh(2, devices=jax.devices()[:1]))
    with pytest.raises(ValueError, match="unknown deployment"):
        LLMServer(cfg, params, OPTS_Q, backend="paged", deployment="tpu")
