"""AOT-compile the TPU data plane against a real v5e topology.

The centerpiece transport — ``lax.ragged_all_to_all`` over ICI
(parallel/exchange.py) — cannot execute on the CPU validation mesh
(XLA:CPU lacks the opcode) and single-chip hardware runs bypass the
exchange entirely. These tests close that gap as far as software can
without a multi-chip slice: the full XLA:TPU + Mosaic compiler stack runs
here against an ahead-of-time ``v5e:2x4`` topology, validating opcode
support, SPMD partitioning, layouts, and the Pallas ring kernel's
compiled-mode path (including the WAR-race neighbor barrier that
interpret mode cannot emulate, ops/ring_exchange.py:79). Execution parity
with the gather oracle is asserted wherever the running backend honors
the opcode (skipped until one does — the reference's analogous most-
tested path is its verbs engine, java/RdmaChannel.java).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS = "shuffle"


@functools.lru_cache(maxsize=1)
def _tpu_mesh():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc("v5e:2x4")
    except Exception as e:  # noqa: BLE001 — no libtpu compiler in this env
        return None, str(e)
    return Mesh(np.array(topo.devices).reshape(8), (AXIS,)), ""


@pytest.fixture
def tpu_mesh():
    mesh, err = _tpu_mesh()
    if mesh is None:
        pytest.skip(f"TPU AOT topology unavailable: {err[:120]}")
    return mesh


def _lower_compile(jitted, *args):
    lowered = jitted.lower(*args)
    text = lowered.as_text()
    compiled = lowered.compile()
    assert compiled is not None
    return text, compiled


def test_native_exchange_compiles_with_ragged_opcode(tpu_mesh):
    """The full 8-device native exchange AOT-compiles for v5e and actually
    lowers to the ragged-all-to-all opcode (not a silent decomposition)."""
    from sparkrdma_tpu.parallel.exchange import make_shuffle_exchange

    exchange = make_shuffle_exchange(tpu_mesh, AXIS, impl="native",
                                     out_factor=2)
    sh = NamedSharding(tpu_mesh, P(AXIS))
    data = jax.ShapeDtypeStruct((8 * 128, 8), jnp.uint32, sharding=sh)
    dest = jax.ShapeDtypeStruct((8 * 128,), jnp.int32, sharding=sh)
    text, _ = _lower_compile(exchange, data, dest)
    assert "ragged_all_to_all" in text, "native path decomposed away"


def test_terasort_step_compiles_for_tpu(tpu_mesh):
    """The flagship multi-chip step (partition + native ragged exchange +
    sort) passes the real XLA:TPU compiler at v5e layouts."""
    from sparkrdma_tpu.models.terasort import TeraSortConfig, make_terasort_step

    cfg = TeraSortConfig(rows_per_device=256, payload_words=24, out_factor=2)
    step = make_terasort_step(tpu_mesh, AXIS, cfg)  # auto -> native on tpu
    rows = jax.ShapeDtypeStruct((8 * cfg.rows_per_device, 25), jnp.uint32,
                                sharding=NamedSharding(tpu_mesh, P(AXIS)))
    text, _ = _lower_compile(step, rows)
    assert "ragged_all_to_all" in text


def test_ring_kernel_mosaic_compiles(tpu_mesh):
    """The hand-scheduled Pallas ring (remote DMAs + neighbor barrier)
    passes Mosaic in compiled mode — the barrier code interpret mode can't
    reach gets compiler-validated here."""
    from sparkrdma_tpu.ops.ring_exchange import make_ring_all_to_all

    a2a = make_ring_all_to_all(tpu_mesh, AXIS, interpret=False)
    x = jax.ShapeDtypeStruct((8, 8, 8, 128), jnp.uint32,
                             sharding=NamedSharding(tpu_mesh, P(AXIS)))
    _lower_compile(a2a, x)


def test_chunked_ring_round_compiles(tpu_mesh):
    """The production wrapper of the ring (chunked exchange, impl='ring')
    compiles end-to-end for v5e."""
    from sparkrdma_tpu.parallel.exchange import make_chunked_exchange

    round_fn = make_chunked_exchange(tpu_mesh, AXIS, quota=128, impl="ring")
    sh = NamedSharding(tpu_mesh, P(AXIS))
    grouped = jax.ShapeDtypeStruct((8 * 1024, 8), jnp.uint32, sharding=sh)
    counts = jax.ShapeDtypeStruct((8 * 8,), jnp.int32, sharding=sh)
    _lower_compile(round_fn, grouped, counts, 0)


def test_2d_mesh_exchange_compiles(tpu_mesh):
    """dp x shuffle composition (the embedding a host engine uses) compiles
    for v5e — collectives ride the inner mesh axis only."""
    from sparkrdma_tpu.parallel.exchange import shuffle_shard

    devs = np.array(tpu_mesh.devices).reshape(2, 4)
    mesh2 = Mesh(devs, ("dp", AXIS))

    @jax.jit
    @functools.partial(shard_map, mesh=mesh2,
                       in_specs=(P("dp", AXIS),) * 2,
                       out_specs=P("dp", AXIS))
    def exchange2d(data, dest):
        received, _, _, _ = shuffle_shard(data[0], dest[0], AXIS, 4,
                                          impl="native")
        return received[None]

    sh = NamedSharding(mesh2, P("dp", AXIS))
    data = jax.ShapeDtypeStruct((2, 4 * 64), jnp.int32, sharding=sh)
    dest = jax.ShapeDtypeStruct((2, 4 * 64), jnp.int32, sharding=sh)
    text, _ = _lower_compile(exchange2d, data, dest)
    assert "ragged_all_to_all" in text


def test_tpcds_step_compiles_for_tpu(tpu_mesh):
    """The 5-exchange star-join step (the TPC-DS-class plan) compiles for
    v5e with all exchanges on the native opcode."""
    from sparkrdma_tpu.models.tpcds import TpcdsConfig, make_tpcds_step

    cfg = TpcdsConfig(fact_rows_per_device=256, dim1_size=128, dim2_size=128,
                      num_groups=64)
    step = make_tpcds_step(tpu_mesh, AXIS, cfg)
    sh = NamedSharding(tpu_mesh, P(AXIS))
    fact = jax.ShapeDtypeStruct((8 * 256, 3), jnp.uint32, sharding=sh)
    dim = jax.ShapeDtypeStruct((8 * 16, 2), jnp.uint32, sharding=sh)
    text, _ = _lower_compile(step, fact, dim, dim)
    assert text.count("ragged_all_to_all") >= 5


def test_scale_up_topologies_resolve_and_compile():
    """The v5e compiler accepts ragged-all-to-all only up to 16 chips
    (32+ have limited ICI routing and reject the opcode — discovered by
    this AOT suite). resolve_impl probe-compiles per mesh, so the
    flagship step must pick native at 16 chips and degrade to the dense
    fixed-slot transport at 64 — compiling at BOTH scales."""
    from jax.experimental import topologies

    from sparkrdma_tpu.models.terasort import TeraSortConfig, make_terasort_step
    from sparkrdma_tpu.parallel.exchange import resolve_impl

    cfg = TeraSortConfig(rows_per_device=256, payload_words=24, out_factor=2)
    for name, n, native_ok in (("v5e:4x4", 16, True), ("v5e:8x8", 64, False)):
        try:
            topo = topologies.get_topology_desc(name)
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"{name} AOT topology unavailable: {str(e)[:100]}")
        mesh = Mesh(np.array(topo.devices).reshape(n), (AXIS,))
        impl = resolve_impl(mesh, axis_name=AXIS)
        assert impl == ("native" if native_ok else "dense"), (name, impl)
        step = make_terasort_step(mesh, AXIS, cfg)
        rows = jax.ShapeDtypeStruct((n * cfg.rows_per_device, 25),
                                    jnp.uint32,
                                    sharding=NamedSharding(mesh, P(AXIS)))
        text, _ = _lower_compile(step, rows)
        assert ("ragged_all_to_all" in text) == native_ok, name
        if not native_ok:  # the dense transport's all-to-all must survive
            assert "all_to_all" in text, name


@pytest.mark.parametrize("message,want", [
    ("INTERNAL: RET_CHECK failure !op_region_.target()."
     "HasLimitedIciRouting() Ragged all-to-all is currently not supported "
     "in limited ICI routing settings", "dense"),
    ("INTERNAL: libtpu lost its mind", None),
])
def test_resolve_impl_only_forgives_the_routing_rejection(
        tpu_mesh, monkeypatch, message, want):
    """On a TPU mesh only the compiler's limited-ICI-routing rejection
    selects dense; any other probe failure re-raises with the compiler's
    message instead of quietly running another transport."""
    from sparkrdma_tpu.parallel import exchange as exchange_mod

    def refuse(*a, **kw):
        raise RuntimeError(message)

    monkeypatch.setattr(exchange_mod.lax, "ragged_all_to_all", refuse)
    exchange_mod._native_compiles.cache_clear()
    try:
        if want is None:
            with pytest.raises(RuntimeError, match="lost its mind"):
                exchange_mod.resolve_impl(tpu_mesh, axis_name=AXIS)
        else:
            assert exchange_mod.resolve_impl(tpu_mesh, axis_name=AXIS) == want
    finally:
        exchange_mod._native_compiles.cache_clear()


def test_native_parity_where_backend_executes():
    """Bit-identity of impl='native' vs the gather oracle, on any running
    backend that honors the opcode (today: real multi-chip TPU; XLA:CPU
    raises UNIMPLEMENTED and the test skips — the AOT tests above still
    compiler-validate the path)."""
    from sparkrdma_tpu.parallel.exchange import make_shuffle_exchange

    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs >=4 devices")
    n = 4
    mesh = Mesh(np.array(devs[:n]), (AXIS,))
    sh = NamedSharding(mesh, P(AXIS))
    rng = np.random.default_rng(3)
    cap = 64
    data = rng.integers(0, 2**31, size=(n * cap, 8), dtype=np.int32)
    dest = rng.integers(0, n, size=(n * cap,)).astype(np.int32)
    data_d, dest_d = (jax.device_put(x, sh) for x in (data, dest))

    native = make_shuffle_exchange(mesh, AXIS, impl="native", out_factor=2)
    try:
        got = jax.block_until_ready(native(data_d, dest_d))
    except Exception as e:  # noqa: BLE001
        if "not supported" in str(e) or "UNIMPLEMENTED" in str(e):
            pytest.skip(f"backend lacks ragged-all-to-all: {str(e)[:100]}")
        raise
    oracle = make_shuffle_exchange(mesh, AXIS, impl="gather", out_factor=2)
    want = jax.block_until_ready(oracle(data_d, dest_d))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# the packed row permute (ops/row_permute.py) at the benchmark's shapes
# ---------------------------------------------------------------------------

MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'
PERMUTE_SCOPES = ("row_gather/pack", "row_gather/permute",
                  "row_gather/unpack")


@functools.lru_cache(maxsize=1)
def _v5e_2x2():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc("v5e:2x2").devices, ""
    except Exception as e:  # noqa: BLE001 — no libtpu compiler in this env
        return None, str(e)


@pytest.fixture
def v5e_host():
    """The four described chips of one v5e host (the benchmark's)."""
    devices, err = _v5e_2x2()
    if devices is None:
        pytest.skip(f"TPU AOT topology unavailable: {err[:120]}")
    return devices


def _fused_step_args(devices, rows_per_chip, row_words, partition):
    from sparkrdma_tpu.parallel.device_plane import make_fused_step

    mesh = Mesh(np.array(devices), (AXIS,))
    sh = NamedSharding(mesh, P(AXIS))
    n = len(devices) * rows_per_chip
    args = [jax.ShapeDtypeStruct((n, row_words), jnp.uint32, sharding=sh)]
    if partition == "dest":
        args.append(jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sh))
    key_words = 2 if partition == "dest" else 1
    step = make_fused_step(mesh, AXIS, row_words, partition=partition,
                           key_words=key_words)
    return step, args


# the compiler's own count of a chip's temporaries. One chip: the packed
# operand and the packed result, 1.37 GB each (2,792,655,872 when written);
# a padded row-major copy of the rows alone would be 5.5 GB. Four chips:
# the parent's step already counts 8,247,000,064 (the receive buffer rides
# the collective at 128 lanes); the packed form added 612,864 to it.
@pytest.mark.parametrize("chips,rows_per_chip,gathers,temp_limit", [
    (1, 10_737_418, 1, 3_000_000_000),      # fused_1chip
    (4, 5_368_709, 2, 8_400_000_000),       # fused_4chip
])
def test_fused_step_compiles_with_the_packed_permute(
        v5e_host, chips, rows_per_chip, gathers, temp_limit):
    """At the fused cells' shapes every row gather is the three Mosaic
    kernels, named under ``row_gather``, and no 128-lane-padded copy of
    the rows appears among the temporaries."""
    step, args = _fused_step_args(v5e_host[:chips], rows_per_chip, 25,
                                  "range")
    compiled = step.lower(*args).compile()
    assert step.row_moves == ["packed"] * gathers
    calls = [line for line in compiled.as_text().splitlines()
             if MOSAIC_CALL in line]
    assert len(calls) == 3 * gathers
    for scope in PERMUTE_SCOPES:
        assert sum(scope in line for line in calls) == gathers, scope
    assert compiled.memory_analysis().temp_size_in_bytes < temp_limit


@pytest.mark.parametrize("rows_per_chip,row_words,partition", [
    (111_848, 25, "dest"),      # a round of spi_device_1chip: under the edge
    (16_777_280, 2, "dest"),    # pagerank_1chip's rows: too narrow
])
def test_fused_step_without_the_packed_permute(v5e_host, rows_per_chip,
                                               row_words, partition):
    """Where ``row_move_form`` says ``take`` the step holds no Mosaic call.
    The form is chosen while tracing, so the lowered text says it all (the
    compile, a minute of sort networks, would add nothing)."""
    step, args = _fused_step_args(v5e_host[:1], rows_per_chip, row_words,
                                  partition)
    text = step.lower(*args).as_text(debug_info=True)
    assert step.row_moves == ["take"]
    assert "tpu_custom_call" not in text
    assert "row_gather" in text


@pytest.mark.parametrize("row_words,mosaic_calls", [
    (8, 3), (24, 3), (33, 3), (64, 3), (65, 0), (100, 0), (128, 0)])
def test_permute_rows_compiles_at_every_width_class(v5e_host, row_words,
                                                    mosaic_calls):
    """The three kernels compile for the chip at 4 and at 2 records to a
    packed row, not only at the cells' 25 words; over 64 words the program
    is XLA's gather. (At 1 record to a row the permute's index block was
    512 words, which Mosaic refuses beside XLA's 1,024-word SMEM tiles:
    found on the chip, PR 29, where jnp.take was as fast at those
    widths.)"""
    from jax.sharding import SingleDeviceSharding

    from sparkrdma_tpu.ops.row_permute import permute_rows

    one = SingleDeviceSharding(v5e_host[0])
    n = 2_000_003       # past the edge at 8 words, and no multiple of a block
    rows = jax.ShapeDtypeStruct((n, row_words), jnp.uint32, sharding=one)
    order = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one)
    chosen = []
    compiled = jax.jit(
        lambda r, o: permute_rows(r, o, "tpu", chosen)).lower(
            rows, order).compile()
    assert chosen == ["packed" if mosaic_calls else "take"]
    assert compiled.as_text().count(MOSAIC_CALL) == mosaic_calls


def test_narrow_rows_ride_the_sort_on_a_tpu_mesh(v5e_host):
    """``group_by_destination`` on 8-byte rows: on a TPU mesh too the rows
    are operands of the one stable sort, no Mosaic call, no gather."""
    from sparkrdma_tpu.parallel.exchange import (
        group_by_destination,
        row_mover,
    )

    mesh = Mesh(np.array(v5e_host[:1]), (AXIS,))
    sh = NamedSharding(mesh, P(AXIS))

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=(P(AXIS), P(AXIS)),
                       out_specs=P(AXIS))
    def grouped(rows, dest):
        return group_by_destination(rows, dest, 1, move)[0]

    chosen = []
    move = row_mover(mesh, chosen)
    rows = jax.ShapeDtypeStruct((16_777_280, 2), jnp.uint32, sharding=sh)
    dest = jax.ShapeDtypeStruct((16_777_280,), jnp.int32, sharding=sh)
    text = grouped.lower(rows, dest).as_text()
    assert "tpu_custom_call" not in text
    assert '"stablehlo.gather"(' not in text
    assert text.count('"stablehlo.sort"(') == 1
    assert chosen == ["sort"]


def _pagerank_step_args(devices, num_e=1 << 16, num_v=1000):
    """``make_pagerank_step`` for described chips, ``num_e`` edges and
    ``num_v`` vertices a chip, and the shapes it takes."""
    from sparkrdma_tpu.models.pagerank import (
        PageRankConfig,
        make_pagerank_step,
    )

    n = len(devices)
    mesh = Mesh(np.array(devices), (AXIS,))
    sh = NamedSharding(mesh, P(AXIS))
    step = make_pagerank_step(
        mesh, AXIS, PageRankConfig(num_vertices=n * num_v,
                                   edges_per_device=num_e))
    return step, (
        jax.ShapeDtypeStruct((n * num_e, 2), jnp.int32, sharding=sh),
        jax.ShapeDtypeStruct((n * num_v,), jnp.float32, sharding=sh),
        jax.ShapeDtypeStruct((n * num_v,), jnp.float32, sharding=sh))


def _q95_step_args(devices):
    from sparkrdma_tpu.models.tpcds_queries import Q95Config, make_q95_step

    n, ws, wr = len(devices), 6144, 614
    mesh = Mesh(np.array(devices), (AXIS,))
    sh, whole = NamedSharding(mesh, P(AXIS)), NamedSharding(mesh, P())
    cfg = Q95Config(ws_rows_per_device=ws, wr_rows_per_device=wr,
                    num_orders=n * 512, survivor_capacity=512)
    u32, i32 = jnp.uint32, jnp.int32
    return make_q95_step(mesh, AXIS, cfg), (
        tuple(jax.ShapeDtypeStruct((n * ws,), d, sharding=sh)
              for d in (u32, u32) + (i32,) * 6),
        tuple(jax.ShapeDtypeStruct((n * wr,), u32, sharding=sh)
              for _ in range(2)),
        tuple(jax.ShapeDtypeStruct((k,), i32, sharding=whole)
              for k in (cfg.num_dates, cfg.num_addresses, cfg.num_sites)))


# sorts: the grouping's one a row set (it was its argsort), and the q95
# join's two. scatters: PageRank's scatter-add of the contributions, q95's
# mark of the qualifying orders; a ``bincount`` would be one more a row set
@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("make,groupings,sorts,scatters", [
    (_pagerank_step_args, 1, 1, 1), (_q95_step_args, 3, 5, 1)])
def test_narrow_row_steps_group_with_one_sort_and_nothing_else(
        v5e_host, make, groupings, sorts, scatters, chips):
    """The PageRank and q95 steps as lowered for described chips: every
    grouping rode its sort (``row_moves``), so the program holds no gather
    under ``row_gather``, no ``bincount``'s scatter-add and no argsort,
    and exactly the sorts it held before. (Lowered only: the form is
    chosen while tracing.)"""
    step, args = make(v5e_host[:chips])
    text = step.lower(*args).as_text(debug_info=True)
    assert step.row_moves == ["sort"] * groupings
    assert "/row_sort/sort" in text
    for gone in ("row_gather", "bincount", "argsort", "tpu_custom_call"):
        assert gone not in text, gone
    assert text.count('"stablehlo.sort"(') == sorts
    assert text.count('"stablehlo.scatter"(') == scatters


def _without_kernel_bodies(text):
    """A lowered text less its Mosaic kernels' serialized bodies, which
    hold the checkout's path and the call stack's line numbers."""
    import re

    return re.sub(r'\\22body\\22: \\22[^\\]*\\22', "BODY", text)


# sha256 of the lowered text (kernel bodies masked) of the three TeraSort
# cells' steps at their sizes, as PR 33's tree lowers them: these cells
# bypass ``group_by_destination`` (range mode never calls it; ``dest`` mode
# on one device sorts by key), so a change to the grouping leaves them
# byte for byte. A PR that means to change a fused step renews the pins.
@pytest.mark.parametrize("chips,rows_per_chip,partition,pinned", [
    (1, 10_737_418, "range",        # fused_1chip
     "75fa51e8bc039efa0b3bf005eb7025ef61917638b6134d2249e8fa79bab2bce1"),
    (4, 5_368_709, "range",         # fused_4chip
     "49777b4d7ecbaba8be349ccb82226b10b1d3a266d67759ec5116bd1c66315575"),
    (1, 111_848, "dest",            # a round of spi_device_1chip
     "44aedb15a8e0ff87186b0d7005e8e0eaa4d27c261a5b03a23b3aad017c075a31"),
])
def test_bypass_cells_lower_to_the_pinned_programs(
        v5e_host, chips, rows_per_chip, partition, pinned):
    import hashlib

    step, args = _fused_step_args(v5e_host[:chips], rows_per_chip, 25,
                                  partition)
    text = _without_kernel_bodies(step.lower(*args).as_text())
    assert hashlib.sha256(text.encode()).hexdigest() == pinned


def test_pagerank_contrib_compiles_to_one_gather_of_the_table(v5e_host):
    """The chip's compiler keeps the per-vertex quotient as a table of its
    own and reads it with the one per-edge gather of ``pagerank.contrib``:
    it does not pull the divide back into the gather, which would read
    ``ranks`` and ``out_deg`` an edge again. (The cell's degree, 35.8, at a
    sixteenth of its edges: half a minute of compile.)"""
    num_e, num_v = 1_048_576, 29_297
    step, args = _pagerank_step_args(v5e_host[:1], num_e, num_v)
    lines = step.lower(*args).compile().as_text().splitlines()
    gathers = [i for i, line in enumerate(lines)
               if " gather(" in line and "/pagerank.contrib/" in line]
    assert len(gathers) == 1
    table = lines[gathers[0]].split(" gather(")[1].split(",")[0]
    # the operand's own line, above the gather in its fused computation
    shape, = [line.split(" = ")[1].split("{")[0]
              for line in lines[:gathers[0]]
              if line.strip().startswith(f"{table} = ")]
    assert shape == f"f32[{num_v}]"
    divide, = [line for line in lines if " divide(" in line
               and f" f32[{num_v}]" in line]
    assert "/pagerank.contrib/" in divide
    assert not any(" divide(" in line and f" f32[{num_e}]" in line
                   for line in lines)


@pytest.mark.parametrize("row_words,wire_words", [
    (2, 128), (3, 128), (4, 128), (7, 128), (8, 8), (25, 25)])
def test_narrow_rows_cross_a_tpu_mesh_as_wire_rows(v5e_host, row_words,
                                                   wire_words):
    """On four described chips ``shuffle_records_shard`` hands the ragged
    all-to-all rows of 128 lanes where the records are under 8 words, and
    the rows as they are from 8 words on: the rule reads the row's width,
    no option. (Lowered only: the form is chosen while tracing.)"""
    import re

    from sparkrdma_tpu.parallel import exchange

    mesh = Mesh(np.array(v5e_host), (AXIS,))
    sh = NamedSharding(mesh, P(AXIS))
    per_chip = 1000
    move = exchange.row_mover(mesh)

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=(P(AXIS), P(AXIS)),
                       out_specs=(P(AXIS), P(AXIS)))
    def shuffle(rows, dest):
        fill = jnp.zeros((4, row_words), jnp.uint32)
        records, counts, _, _ = exchange.shuffle_records_shard(
            rows, dest, fill, AXIS, 4, 2, "native", move)
        return records, counts

    lowered = shuffle.lower(
        jax.ShapeDtypeStruct((4 * per_chip, row_words), jnp.uint32,
                             sharding=sh),
        jax.ShapeDtypeStruct((4 * per_chip,), jnp.int32, sharding=sh))
    text = lowered.as_text()
    sent, = re.findall(
        r"ragged_all_to_all.*?\(tensor<(\d+)x(\d+)xui32>, tensor<(\d+)x",
        text)
    capacity = exchange.record_capacity(per_chip, row_words, 4, 2)
    if wire_words == 128:
        rows_out = exchange.wire_rows(per_chip, row_words, 4)
        assert sent == (str(rows_out), "128", str(2 * rows_out))
        assert capacity == 2 * rows_out * (128 // row_words)
    else:
        assert sent == (str(per_chip), str(row_words), str(2 * per_chip))
        assert capacity == 2 * per_chip
    assert lowered.out_info[0].shape == (4 * capacity, row_words)


def _als_half_step_args(devices, side, links, chunks):
    """``make_als_half_step`` for described chips at ``als_4chip``'s size
    (480,189 users, 17,770 items, rank 10), and the shapes it takes: an
    OutBlock of ``links`` rows and ``chunks`` chunks of 32 x 32,768 rating
    slots a chip."""
    from sparkrdma_tpu.models.als import (
        TILE,
        ALSConfig,
        ids_per_block,
        make_als_half_step,
    )

    n, nt = len(devices), 1 << 15
    cfg = ALSConfig(num_users=480_189, num_items=17_770)
    num_dst, num_src = ((cfg.num_items, cfg.num_users) if side == "item"
                        else (cfg.num_users, cfg.num_items))
    mesh = Mesh(np.array(devices), (AXIS,))
    sh = NamedSharding(mesh, P(AXIS))

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sh)

    return make_als_half_step(mesh, AXIS, cfg, side), (
        shape((n * ids_per_block(num_src, n), cfg.rank), jnp.float32),
        shape((n * links,), jnp.int32), shape((n * links,), jnp.int32),
        shape((n * chunks, TILE, nt), jnp.int32),
        shape((n * chunks, TILE, nt), jnp.float32),
        shape((n * chunks * nt,), jnp.int32),
        shape((n * ids_per_block(num_dst, n),), jnp.int32))


def _whiles_that_gather(hlo_text):
    """Names of the ``while`` ops of a compiled module's text whose body,
    or a computation it calls, holds a ``gather``: the shape the v5e ran
    wrongly when the gather's table was loop-carried in VMEM (``PERF.md``
    section 7.14)."""
    import re

    bodies, name = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif name is not None:
            bodies[name].append(line)

    def gathers(computation, seen):
        if computation in seen or computation not in bodies:
            return False
        seen.add(computation)
        return any(
            " gather(" in line
            or any(gathers(callee, seen) for callee in re.findall(
                r"(?:calls|to_apply|body|condition)=(%[\w.-]+)", line))
            for line in bodies[computation])

    return [re.match(r"\s*(?:ROOT )?(%[\w.-]+)", line).group(1)
            for lines in bodies.values() for line in lines
            if " while(" in line
            and gathers(re.search(r"body=(%[\w.-]+)", line).group(1), set())]


def test_whiles_that_gather_reads_a_modules_text():
    text = """\
%fused (p: u32[8,10], i: s32[4]) -> u32[4,10] {
  %g = u32[4,10] gather(%p, %i), offset_dims={1}
}

%body.1 (t: (u32[8,10], s32[4])) -> (u32[8,10], s32[4]) {
  %f = u32[4,10] fusion(%a, %b), kind=kCustom, calls=%fused
}

%body.2 (t: (s32[])) -> (s32[]) {
  %add = s32[] add(%x, %y)
}

ENTRY %main (a: u32[8,10]) -> u32[4,10] {
  %while.7 = (u32[8,10], s32[4]) while(%t), condition=%cond.1, body=%body.1
  ROOT %while.9 = (s32[]) while(%u), condition=%cond.2, body=%body.2
}
"""
    assert _whiles_that_gather(text) == ["%while.7"]


# the compiler's own count of a chip's temporaries: 0.789 GB where items
# are solved (the 492 MB receive buffer at 128 lanes is most of it), 1.244
# GB where users are (the tile sums: 851,968 rows of 65 words at 128
# lanes, twice), when written (0.877 / 1.347 GB before PR 37 clipped the
# gather). 25 M gathered rows of 10 words whole and padded to 128 lanes
# would be 12.8 GB: a chunk of them is live at a time.
@pytest.mark.parametrize("side,links,chunks,table_rows,temp_limit", [
    ("item", 480_192, 25, 480_189, 1_200_000_000),
    ("user", 17_772, 26, 17_770, 1_350_000_000)])
def test_als_half_steps_compile_at_the_cells_size(
        v5e_host, side, links, chunks, table_rows, temp_limit):
    """An ALS half-step on the four described chips of one v5e host at
    ``als_4chip``'s size: the factor rows cross through the ragged
    all-to-all one to a 128-lane wire row, the grouping and the
    per-rating gather are ``take`` (one gather a chunk, in straight-line
    code: no ``while``, and none whose body gathers; its operand is the
    rows that can arrive, not the whole ``out_factor`` 2 buffer; what it
    gathers is written once: no select and no bit-cast runs over a chunk
    of rows padded to 128 lanes), and no Mosaic kernel runs."""
    import re

    step, args = _als_half_step_args(v5e_host, side, links, chunks)
    compiled = step.lower(*args).compile()
    assert step.row_moves == ["take", "take"]
    text = compiled.as_text()
    assert MOSAIC_CALL not in text and " while(" not in text
    assert _whiles_that_gather(text) == []
    assert f"u32[{2 * links},1,128]" in text      # the receive buffer
    gathers = [line for line in text.splitlines()
               if " gather(" in line and "/als.gather/" in line]
    assert len(gathers) == chunks
    assert f"f32[{table_rows},10]" in text        # the gather's operand
    # a chunk's rows as gathered, 512 MB at 128 lanes where the table is
    # small enough to be gathered as rows: one op a chunk writes them
    entry = text[text.index("ENTRY "):]
    written = re.findall(r"= [fu]32\[1048576,10\]\S* (?!bitcast\()[\w-]+\(",
                         entry)
    assert len(written) == chunks
    assert compiled.memory_analysis().temp_size_in_bytes < temp_limit


# the compiler's own count of a chip's temporaries: 0.807 GB on one chip
# and 0.941 GB on four when written (0.672 and 0.807 with the masked
# scatter-add over the whole receive buffer); the receive buffer of
# 33,554,560 records is 0.27 GB a chip
@pytest.mark.parametrize("chips", [1, 4])
def test_pagerank_accumulate_is_one_scatter_in_a_loop_at_the_cells_size(
        v5e_host, chips):
    """The PageRank step at the cells' size (16,777,216 edges and 468,750
    vertices a chip) on described v5e chips: the scatter-add is the
    program's one scatter, in the body of ``pagerank.accumulate``'s loop
    over the receive buffer's chunks, and no loop of that scope gathers
    (the shape the v5e ran wrongly, ``PERF.md`` section 7.14)."""
    import re

    step, args = _pagerank_step_args(v5e_host[:chips], 16_777_216, 468_750)
    compiled = step.lower(*args).compile()
    text = compiled.as_text()
    scatters = [line for line in text.splitlines() if " scatter(" in line]
    assert len(scatters) == 1
    assert "/pagerank.accumulate/while/body/" in scatters[0]
    loops = [re.match(r"\s*(?:ROOT )?(%[\w.-]+)", line).group(1)
             for line in text.splitlines()
             if " while(" in line and "/pagerank.accumulate/" in line]
    assert loops
    assert not set(loops) & set(_whiles_that_gather(text))
    assert compiled.memory_analysis().temp_size_in_bytes < 1_000_000_000
