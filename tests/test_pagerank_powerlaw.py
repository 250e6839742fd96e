"""The PageRank deployment on the normal path: the seeded power-law graph,
the job entry over a resident graph, its spans, counters and scopes, and
the system against the benchmark's float64 reference on one virtual device
and on four (the one-chip cut's tie to the deployment)."""

import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import reference_pagerank  # noqa: E402
from sparkrdma_tpu.models.pagerank import (  # noqa: E402
    PageRankConfig,
    PageRankJob,
    accumulate_chunk,
    make_pagerank_step,
    place_graph,
    powerlaw_graph,
)
from sparkrdma_tpu.parallel import exchange  # noqa: E402
from sparkrdma_tpu.utils.trace import ACCOUNTING_ARGS, Tracer  # noqa: E402

AXIS = "shuffle"
ZIPF_S = 0.9


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), (AXIS,))


def _events(tracer, tmp_path):
    import json

    path = str(tmp_path / "trace.json")
    tracer.dump(path)
    with open(path) as f:
        return json.load(f)["traceEvents"]


# -- the generator ------------------------------------------------------------

def test_powerlaw_graph_is_deterministic_in_the_seed():
    cfg = PageRankConfig(num_vertices=4096, edges_per_device=5000)
    a = powerlaw_graph(cfg, 4, seed=2**31 + 11, zipf_s=ZIPF_S)
    b = powerlaw_graph(cfg, 4, seed=2**31 + 11, zipf_s=ZIPF_S)
    c = powerlaw_graph(cfg, 4, seed=2**31 + 12, zipf_s=ZIPF_S)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], c[0])


def test_powerlaw_graph_is_bounded_and_places_edges_on_their_source():
    devices, per_dev, num_v = 4, 6000, 1024
    cfg = PageRankConfig(num_vertices=num_v, edges_per_device=per_dev)
    edges, ranks, out_deg = powerlaw_graph(cfg, devices, seed=5,
                                           zipf_s=ZIPF_S)
    assert edges.shape == (devices * per_dev, 2) and edges.dtype == np.int32
    assert edges.min() >= 0 and edges.max() < num_v
    owner = edges[:, 0] // (num_v // devices)
    np.testing.assert_array_equal(owner, np.repeat(np.arange(devices),
                                                   per_dev))
    np.testing.assert_array_equal(
        out_deg, np.bincount(edges[:, 0], minlength=num_v))
    assert out_deg.dtype == np.float32 and out_deg.sum() == len(edges)
    np.testing.assert_allclose(ranks, 1.0 / num_v)
    # the hubs are spread over the id range, and so over the devices
    top = np.argsort(np.bincount(edges[:, 1], minlength=num_v))[-8:]
    assert len(set(top // (num_v // devices))) > 1


def test_powerlaw_graph_top_hub_is_the_zipf_expectation():
    num_v, total = 4096, 200_000
    cfg = PageRankConfig(num_vertices=num_v, edges_per_device=total)
    edges, _, _ = powerlaw_graph(cfg, 1, seed=9, zipf_s=ZIPF_S)
    in_degree = np.sort(np.bincount(edges[:, 1], minlength=num_v))[::-1]
    weights = np.arange(1, num_v + 1, dtype=np.float64) ** -ZIPF_S
    expect = total * weights / weights.sum()
    assert 0.8 * expect[0] < in_degree[0] < 1.25 * expect[0]
    # and the tail is a power law's, not a uniform graph's
    assert in_degree[0] > 20 * np.median(in_degree)
    assert 0.8 * expect[:64].sum() < in_degree[:64].sum() < 1.25 * expect[
        :64].sum()


# -- the system against the benchmark's reference -----------------------------

@pytest.mark.parametrize("devices", [1, 4])
def test_job_equals_the_float64_reference(devices):
    """One device (the cell's cut) and four (the deployment's form) give
    the uncut reference's ranks: same seed, same whole graph."""
    num_v, total = 1024, 16384
    cfg = PageRankConfig(num_vertices=num_v,
                         edges_per_device=total // devices, out_factor=2)
    edges, _, out_deg = powerlaw_graph(cfg, devices, seed=2**31 + 3,
                                       zipf_s=ZIPF_S)
    mesh = _mesh(devices)
    job = PageRankJob(mesh, AXIS, cfg, iterations=3)
    ranks = np.asarray(job(place_graph(mesh, AXIS, edges, out_deg)))
    problems, readings = reference_pagerank.pagerank_report(
        ranks, edges, num_v, cfg.damping, 3)
    assert problems == []
    assert readings["bound_share"] < 0.5
    assert readings["relative_error"] < 1e-5
    assert abs(ranks.sum() - 1.0) < 1e-3


def test_a_hub_past_out_factor_raises():
    devices, per_dev, num_v = 4, 512, 256
    cfg = PageRankConfig(num_vertices=num_v, edges_per_device=per_dev,
                         out_factor=2)
    edges, _, out_deg = powerlaw_graph(cfg, devices, seed=1, zipf_s=ZIPF_S)
    edges[:, 1] = 7    # every edge of every device points at one vertex
    mesh = _mesh(devices)
    job = PageRankJob(mesh, AXIS, cfg, iterations=2)
    with pytest.raises(OverflowError, match="supersteps \\[0, 1\\]"):
        job(place_graph(mesh, AXIS, edges, out_deg))


# -- the job entry -------------------------------------------------------------

def test_job_returns_a_resident_array_and_compiles_once():
    import jax.monitoring

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    cfg = PageRankConfig(num_vertices=512, edges_per_device=2048)
    mesh = _mesh(4)
    edges, _, out_deg = powerlaw_graph(cfg, 4, seed=3, zipf_s=ZIPF_S)
    graph = place_graph(mesh, AXIS, edges, out_deg)
    assert graph.num_edges == len(edges)
    assert graph.max_in_degree == np.bincount(edges[:, 1]).max()
    job = PageRankJob(mesh, AXIS, cfg, iterations=3)
    first = job(graph)
    after_first = len(compiles)
    second = job(graph)
    assert after_first >= 1 and len(compiles) == after_first
    for ranks in (first, second):
        assert isinstance(ranks, jax.Array)
        assert ranks.shape == (512,) and len(ranks.sharding.device_set) == 4
    # a job starts from 1/V, whatever the one before it left
    np.testing.assert_array_equal(np.asarray(first), np.asarray(second))


def test_job_spans_and_counters(tmp_path):
    devices, per_dev, num_v = 4, 1000, 512   # 1000: not a whole wire row
    cfg = PageRankConfig(num_vertices=num_v, edges_per_device=per_dev)
    mesh = _mesh(devices)
    edges, _, out_deg = powerlaw_graph(cfg, devices, seed=4, zipf_s=ZIPF_S)
    edges[-10:, 0] = -1    # padding rows are no contributions
    graph = place_graph(mesh, AXIS, edges, out_deg)
    tracer = Tracer()
    PageRankJob(mesh, AXIS, cfg, iterations=3, tracer=tracer)(graph)
    events = _events(tracer, tmp_path)
    spans = {e["name"]: e for e in events if e.get("ph") == "X"}
    assert set(spans) == {"pagerank.job", "pagerank.dispatch",
                          "pagerank.wait"}
    job = spans["pagerank.job"]
    # what the caller gave; the tracer adds its accounting beside it
    own = {k: v for k, v in job["args"].items()
           if k not in ACCOUNTING_ARGS}
    fill = own.pop("accumulate_fill")
    assert own == {"iterations": 3, "edges": len(edges) - 10,
                   "vertices": num_v,
                   "received": [len(edges) - 10] * 3,
                   # 8-byte rows: jnp.take on any platform
                   "row_move": "sort"}
    for inner in ("pagerank.dispatch", "pagerank.wait"):
        assert job["ts"] <= spans[inner]["ts"]
        assert (spans[inner]["ts"] + spans[inner]["dur"]
                <= job["ts"] + job["dur"])
    counters = {e["name"]: e["args"]["value"] for e in events
                if e.get("ph") == "C"}
    assert counters["pagerank.max_in_degree"] == graph.max_in_degree
    # most records any device received over its receive capacity, which
    # the exchange states: 64 records to a wire row (1000 -> 16 rows of
    # 64, and one a destination)
    assert exchange.wire_rows(per_dev, 2, devices) == 16 + devices
    capacity = exchange.record_capacity(per_dev, 2, devices, cfg.out_factor)
    assert capacity == cfg.out_factor * (16 + devices) * 64
    assert 64 / capacity <= counters["pagerank.recv_fill"] <= 1.0
    # the slots the accumulate's loop read: whole chunks past the most
    # records received, no more than the buffer holds
    chunk = accumulate_chunk(capacity)
    assert chunk < capacity
    assert counters["pagerank.recv_fill"] <= fill <= 1.0
    slots = round(fill * capacity)
    assert slots % chunk == 0 or slots == capacity
    most = round(counters["pagerank.recv_fill"] * capacity)
    assert slots == min(-(-most // chunk) * chunk, capacity)


def test_the_three_scopes_name_the_steps_ops():
    cfg = PageRankConfig(num_vertices=256, edges_per_device=1024)
    edges, ranks, out_deg = powerlaw_graph(cfg, 4, seed=1, zipf_s=ZIPF_S)
    step = make_pagerank_step(_mesh(4), AXIS, cfg)
    text = step.lower(edges, ranks, out_deg).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("pagerank.contrib", "pagerank.exchange",
                  "pagerank.accumulate", "pagerank.exchange/row_sort"):
        assert any(f"/{scope}/" in n for n in names), scope
    # the kernels the scopes are for lie under them, and nowhere else
    for scope, kernel in (("pagerank.accumulate", "scatter-add"),
                          ("pagerank.contrib", "gather"),
                          ("pagerank.exchange/row_sort", "sort")):
        assert any(f"/{scope}/" in n and n.endswith(kernel)
                   for n in names), (scope, kernel)
    # the grouping's rows ride its sort: no bincount's scatter-add and no
    # row gather under the exchange
    assert not any(n.endswith("scatter-add")
                   and "pagerank.accumulate" not in n for n in names)
    assert not any("row_gather" in n for n in names)


# -- the contribution phase: one gather of the per-vertex table ---------------

def _awkward_graph(cfg, devices):
    """Padding rows, vertices nobody leaves, and a hub most edges enter."""
    edges, _, _ = powerlaw_graph(cfg, devices, seed=2**31 + 7, zipf_s=ZIPF_S)
    edges[::3, 1] = 5                       # the hub: a long float32 sum
    edges[7::cfg.edges_per_device // 5, 0] = -1
    valid = edges[:, 0] >= 0
    out_deg = np.bincount(edges[valid, 0],
                          minlength=cfg.num_vertices).astype(np.float32)
    assert (~valid).any() and (out_deg == 0).any()
    return edges, out_deg


@functools.partial(jax.jit, static_argnames="damping")
def _two_gather_superstep(edges, ranks, out_deg, damping):
    """The superstep as it stood: ``ranks`` and ``out_deg`` gathered apart
    and divided once an edge. One device's plain program; contributions
    reach a vertex in the edges' order, as the stable grouping and the
    exchange deliver them."""
    src, dst = edges[:, 0], edges[:, 1]
    valid = src >= 0
    src = jnp.where(valid, src, 0)
    contrib = jnp.where(
        valid, ranks[src] / jnp.maximum(out_deg[src], 1.0), 0.0)
    sums = jnp.zeros_like(ranks).at[jnp.where(valid, dst, 0)].add(contrib)
    return (1.0 - damping) / ranks.shape[0] + damping * sums


@pytest.mark.parametrize("impl", ["auto", "dense"])
@pytest.mark.parametrize("devices", [1, 4])
def test_one_table_gather_gives_the_two_gather_ranks_bit_for_bit(
        devices, impl):
    """64 records to a wire row, the form the chip runs, over the
    transport the CPU mesh resolves to and over fixed per-pair slots."""
    num_v, total = 1024, 4096
    cfg = PageRankConfig(num_vertices=num_v,
                         edges_per_device=total // devices, out_factor=4)
    edges, out_deg = _awkward_graph(cfg, devices)
    step = make_pagerank_step(_mesh(devices), AXIS, cfg, impl)
    ranks = want = np.full(num_v, 1.0 / num_v, np.float32)
    for _ in range(3):
        ranks, received, overflowed = step(edges, ranks, out_deg)
        assert not np.asarray(overflowed).any()
        received = np.asarray(received)
        assert received[:, 0].sum() == (edges[:, 0] >= 0).sum()
        # the fill makes whole wire rows of each pair's records
        assert (received[:, 1] >= received[:, 0]).all()
        assert (received[:, 1] % 64 == 0).all()
        want = _two_gather_superstep(edges, want, out_deg, cfg.damping)
    ranks, want = np.asarray(ranks), np.asarray(want)
    assert len(np.unique(ranks)) > num_v // 4     # no trivial fixed point
    np.testing.assert_array_equal(ranks.view(np.uint32),
                                  want.view(np.uint32))


# -- the accumulate's loop over the receive buffer's chunks -------------------

def _chunk_edge_graph(devices, per_dev, to_first, hub):
    """Source device ``s`` sends ``to_first[s]`` of its edges to device 0
    (to one vertex where ``hub``, else spread over its vertices); the rest
    go round the other devices, or are padding where there are none. So
    device 0 receives exactly ``to_first`` records from its senders, each
    sender's filled up to whole wire rows of 64."""
    v_local = 256
    num_v = devices * v_local
    rng = np.random.default_rng([devices, per_dev, *to_first])
    edges = np.empty((devices * per_dev, 2), np.int32)
    for s, k in enumerate(to_first):
        own = edges[s * per_dev:(s + 1) * per_dev]
        own[:, 0] = rng.integers(s * v_local, (s + 1) * v_local, per_dev)
        own[:k, 1] = 5 if hub else rng.integers(0, v_local, k)
        if devices == 1:
            own[k:, 0] = -1
        else:
            rest = per_dev - k
            owner = 1 + np.arange(rest) % (devices - 1)
            own[k:, 1] = owner * v_local + rng.integers(0, v_local, rest)
    valid = edges[:, 0] >= 0
    out_deg = np.bincount(edges[valid, 0],
                          minlength=num_v).astype(np.float32)
    return num_v, edges, out_deg


# per case and device count: (edges a device, out_factor, records each
# sender sends to device 0, one hub), and where device 0's ``total`` lies
# against the chunks (``accumulate_chunk``: 1,024 slots here)
_CHUNK_CASES = {
    "whole_chunks": {1: (4096, 2, [3072], False),
                     4: (4096, 2, [768] * 4, False)},
    "one_record_past_a_chunk": {1: (4096, 2, [3073], False),
                                4: (4096, 2, [768, 768, 768, 769], False)},
    "clamped_last_chunk": {1: (4160, 1, [4160], False),
                           4: (4096, 2, [2112] * 4, False)},
    "hub_within_a_chunk_of_capacity": {1: (4160, 1, [4160], True),
                                       4: (4096, 2, [2176] * 4, True)},
}


@pytest.mark.parametrize("case", sorted(_CHUNK_CASES))
@pytest.mark.parametrize("devices", [1, 4])
def test_accumulate_at_the_chunk_boundaries(devices, case):
    """Where device 0's records end against the accumulate's chunks, two
    supersteps give the reference's ranks within float32's bound and the
    plain single-scatter superstep's bit for bit: no slot is summed twice
    where the last chunk's start is clamped, and none is left out."""
    per_dev, out_factor, to_first, hub = _CHUNK_CASES[case][devices]
    num_v, edges, out_deg = _chunk_edge_graph(devices, per_dev, to_first,
                                              hub)
    cfg = PageRankConfig(num_vertices=num_v, edges_per_device=per_dev,
                         out_factor=out_factor)
    capacity = exchange.record_capacity(per_dev, 2, devices, out_factor)
    chunk = accumulate_chunk(capacity)
    step = make_pagerank_step(_mesh(devices), AXIS, cfg)
    ranks = want = np.full(num_v, 1.0 / num_v, np.float32)
    for _ in range(2):
        ranks, received, overflowed = step(edges, ranks, out_deg)
        assert not np.asarray(overflowed).any()
        want = _two_gather_superstep(edges, want, out_deg, cfg.damping)
    total = int(np.asarray(received)[0, 1])
    assert total == sum(-(-k // 64) * 64 for k in to_first)
    assert 1024 == chunk < capacity
    if case == "whole_chunks":
        assert total % chunk == 0
    elif case == "one_record_past_a_chunk":
        # the last sender's one record past the boundary, then its fill
        assert sum(to_first) % chunk == 1
    elif case == "clamped_last_chunk":
        assert capacity % chunk and total > capacity - capacity % chunk
    else:
        assert total > capacity - chunk
    ranks = np.asarray(ranks)
    problems, readings = reference_pagerank.pagerank_report(
        ranks, edges, num_v, cfg.damping, 2)
    assert problems == [] and readings["bound_share"] < 0.5
    np.testing.assert_array_equal(ranks.view(np.uint32),
                                  np.asarray(want).view(np.uint32))


def _equations(jaxpr, scope=""):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it, with the
    path of named scopes it lies under."""
    for eqn in jaxpr.eqns:
        path = f"{scope}/{eqn.source_info.name_stack}"
        yield path, eqn
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                yield from _equations(inner, path)


@pytest.mark.parametrize("devices", [1, 4])
def test_contrib_is_one_gather_of_a_per_vertex_quotient(devices):
    num_v, per_dev = 256 * devices, 1024
    cfg = PageRankConfig(num_vertices=num_v, edges_per_device=per_dev)
    edges, ranks, out_deg = powerlaw_graph(cfg, devices, seed=1,
                                           zipf_s=ZIPF_S)
    step = make_pagerank_step(_mesh(devices), AXIS, cfg)
    contrib = [(path, eqn) for path, eqn in _equations(
        jax.make_jaxpr(step)(edges, ranks, out_deg).jaxpr)
        if "pagerank.contrib" in path]
    gather, = [eqn for _, eqn in contrib if eqn.primitive.name == "gather"]
    # the float divide is the table's: once a vertex, none an edge
    divide, = [eqn for _, eqn in contrib if eqn.primitive.name == "div"
               and eqn.outvars[0].aval.dtype == np.float32]
    table = divide.outvars[0]
    assert table.aval.shape == (num_v // devices,)
    assert gather.invars[0] is table
    assert gather.outvars[0].aval.shape == (per_dev,)
