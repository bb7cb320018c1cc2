"""Spans and counters of the serving and deployment paths
(``repro.obs``)."""
import jax
import numpy as np
import pytest

from repro import obs
from repro.edge import EdgeSystem
from repro.serve import ServingPolicy


@pytest.fixture(autouse=True)
def clean():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _batch(g, seed, size=200):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, g.num_vertices, size),
            rng.integers(0, g.num_vertices, size))


def _tree(spans):
    """{request id: sorted (name, parent name) pairs}."""
    by_id = {s.span_id: s for s in spans}
    out = {}
    for s in spans:
        parent = by_id[s.parent_id].name if s.parent_id else None
        out.setdefault(s.request_id, []).append((s.name, parent))
    return {k: sorted(v, key=str) for k, v in out.items()}


def test_off_records_nothing_and_returns_the_shared_no_op():
    first = obs.span("repro.a", k=1)
    assert first is obs.NO_SPAN and obs.span("repro.b") is first
    with obs.span("repro.a") as inner:
        assert inner is None
    assert obs.drain() == []
    obs.count("serve.x", 3)         # counters count while off
    assert obs.counters()["serve.x"] == 3


def test_nesting_parent_and_request_ids():
    obs.enable()
    with obs.span("repro.outer", size=4):
        with obs.span("repro.mid"):
            with obs.span("repro.leaf"):
                pass
        with obs.span("repro.sibling"):
            pass
    with obs.span("repro.next"):
        pass
    spans = obs.drain()
    assert obs.drain() == []
    by = {s.name: s for s in spans}
    # recorded in the order they closed
    assert [s.name for s in spans] == ["repro.leaf", "repro.mid",
                                       "repro.sibling", "repro.outer",
                                       "repro.next"]
    outer = by["repro.outer"]
    assert outer.parent_id == 0 and outer.request_id == outer.span_id
    assert outer.attrs == {"size": 4} and by["repro.mid"].attrs is None
    assert by["repro.mid"].parent_id == outer.span_id
    assert by["repro.leaf"].parent_id == by["repro.mid"].span_id
    assert by["repro.sibling"].parent_id == outer.span_id
    assert {by[n].request_id for n in ("repro.mid", "repro.leaf",
                                       "repro.sibling")} == {outer.span_id}
    nxt = by["repro.next"]
    assert nxt.parent_id == 0 and nxt.request_id == nxt.span_id
    assert len({s.span_id for s in spans}) == 5
    for s in spans:
        assert s.end_ns >= s.start_ns
    assert outer.start_ns <= by["repro.leaf"].start_ns
    assert by["repro.leaf"].end_ns <= outer.end_ns
    assert nxt.start_ns >= outer.end_ns


def test_a_full_buffer_counts_what_it_drops(monkeypatch):
    monkeypatch.setattr(obs, "CAPACITY", 3)
    obs.enable()
    for i in range(5):
        with obs.span("repro.s", i=i):
            pass
    spans = obs.drain()
    assert [s.attrs["i"] for s in spans] == [0, 1, 2]
    assert obs.counters()[obs.DROPPED] == 2
    with obs.span("repro.s"):
        pass
    assert len(obs.drain()) == 1
    obs.reset()
    assert obs.DROPPED not in obs.counters()


def test_disable_stops_recording_and_reset_forgets():
    obs.enable()
    with obs.span("repro.kept"):
        obs.disable()                   # closes and is kept
    with obs.span("repro.lost"):
        pass
    assert [s.name for s in obs.drain()] == ["repro.kept"]
    obs.enable()
    with obs.span("repro.forgotten"):
        pass
    obs.count("serve.y")
    obs.reset()
    assert obs.drain() == [] and obs.counters() == {}


def test_xla_compiles_are_counted():
    before = obs.counters().get(obs.COMPILES, 0)
    jax.jit(lambda x: x * 3 + 1)(np.arange(13, dtype=np.float32))
    assert obs.counters().get(obs.COMPILES, 0) > before


ENGINE_SUBMIT = [("repro.dispatch", "repro.submit"),
                 ("repro.fetch", "repro.submit"),
                 ("repro.plan", "repro.submit"),
                 ("repro.route", "repro.submit"),
                 ("repro.submit", None),
                 ("repro.wrap", "repro.submit")]


def test_each_engine_submit_yields_the_span_tree(small_system):
    g, _, system = small_system
    service = system.service(ServingPolicy(engine="replicated"))
    folds = service._MAX_PENDING
    obs.enable()
    for i in range(folds):
        service.submit(*_batch(g, i))
    spans = obs.drain()
    trees = [t for _, t in sorted(_tree(spans).items())]
    assert len(trees) == folds
    # the service's first submit resolves its engine; the 32nd folds
    # the pending queue of result batches into the counters
    assert trees[0] == sorted(ENGINE_SUBMIT
                              + [("repro.engine.build", "repro.plan")],
                              key=str)
    for tree in trees[1:-1]:
        assert tree == sorted(ENGINE_SUBMIT, key=str)
    assert trees[-1] == sorted(ENGINE_SUBMIT + [("repro.fold", "repro.wrap")],
                               key=str)
    assert all(s.name.startswith("repro.") for s in spans)
    c = obs.counters()
    assert c["serve.submits"] == folds and c["serve.pairs"] == folds * 200
    assert c["serve.pad_pairs"] == folds * 56      # 200 pairs -> 256 lanes
    assert c["serve.folds"] == 1 and c["serve.engine_builds"] == 1
    assert "serve.window_batches" not in c


def test_kernel_join_codes_counts_each_quantized_kernel_dispatch(
        small_system):
    from repro.core.quantize import fit_label_spec
    from repro.edge import BatchedQueryEngine
    g, part, system = small_system
    args = (system.center.border_labels.table,
            [srv.augmented for srv in system.servers], part.assignment)
    spec = fit_label_spec(args[0], args[1])
    ss, ts = _batch(g, 2)
    BatchedQueryEngine(*args, use_pallas=True).query(ss, ts)
    BatchedQueryEngine(*args, use_pallas=False, quant=spec).query(ss, ts)
    assert "kernel.join_codes" not in obs.counters()
    codes = BatchedQueryEngine(*args, use_pallas=True, quant=spec)
    for _ in range(3):
        codes.query(ss, ts)
    assert obs.counters()["kernel.join_codes"] == 3


@pytest.mark.parametrize("shard_border", [False, True])
def test_sharded_engine_counts_what_the_mesh_adds(small_system,
                                                  shard_border):
    """On one device every real lane is owned by device 0; the B rows
    are counted only where B is row-sharded, two per cross-district
    lane, and padding lanes count in neither."""
    from repro.core.quantize import fit_label_spec
    from repro.edge import ShardedBatchedEngine
    g, part, system = small_system
    args = (system.center.border_labels.table,
            [srv.augmented for srv in system.servers], part.assignment)
    spec = fit_label_spec(args[0], args[1])
    engine = ShardedBatchedEngine(*args, use_pallas=True,
                                  shard_border=shard_border, quant=spec)
    batches = [_batch(g, k) for k in range(2)]
    for ss, ts in batches:
        engine.query(ss, ts)
    c = obs.counters()
    assert c["shard.max_owner_lanes"] == 2 * 200
    assert c["kernel.join_codes"] == 2
    cross = sum(int((part.assignment[ss] != part.assignment[ts]).sum())
                for ss, ts in batches)
    assert 0 < cross < 400
    if shard_border:
        assert c["shard.border_rows"] == 2 * cross
    else:
        assert "shard.border_rows" not in c


def test_a_bucketed_submit_is_a_window_batch(small_system):
    g, _, system = small_system
    service = system.service(ServingPolicy(use_kernels=False))
    obs.enable()
    service.submit(*_batch(g, 1, size=16))
    (tree,) = _tree(obs.drain()).values()
    assert tree == sorted([("repro.plan", "repro.submit"),
                           ("repro.submit", None),
                           ("repro.window", "repro.submit"),
                           ("repro.wrap", "repro.submit")], key=str)
    assert obs.counters()["serve.window_batches"] == 1


def test_deploy_records_one_server_span_per_district(small_graph):
    g, part = small_graph
    obs.enable()
    system = EdgeSystem.deploy(g, part)
    spans = obs.drain()
    assert [s.name for s in spans] == (["repro.deploy.center"]
                                       + ["repro.deploy.server"]
                                       * part.num_districts)
    assert [s.attrs["district"] for s in spans[1:]] == \
        list(range(part.num_districts))
    assert all(s.parent_id == 0 for s in spans)
    for a, b in zip(spans, spans[1:]):
        assert a.end_ns <= b.start_ns
    assert all(srv.augmented_version == system.center.version
               for srv in system.servers)
