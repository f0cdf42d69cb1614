"""The main path's kernels compile for a TPU v5e that is described, not
attached (PERF.md, PR 21; the `on-chip-measurement` guide, section 2).

These are the programs the CPU backend never builds: the Pallas one-hot
kernel and the branches behind ``jax.default_backend() != "cpu"``, at the
widths TPC-H SF=1 q1/q3 produce, plus the 4-device ``shard_map`` exchange. A
compile that passes is not a chip run (``chip_smoke.py`` is); it guards every
later PR against a kernel the chip's compiler refuses, at no chip time.

Sort programs are kept at capacities that compile in seconds: one large sort
costs this compiler 16-190 s (PERF.md), which is a finding, not a test.

The topology is described inside a module-scoped fixture: only one process may
load the TPU's library, so nothing here touches it at import or collection.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

N = 1 << 20  # the batch capacity q1's partial aggregate sees at SF=1


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps the library away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry written for a described chip cannot be read back without one:
    # the next compile would warn and compile again
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def S(topo):
    """``S(shape, dtype)``: an abstract argument placed on the first chip."""
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, jnp.dtype(dtype), sharding=one
    )


def _compile(fn, *args):
    f = fn if hasattr(fn, "lower") else jax.jit(fn)
    compiled = f.lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 12 << 30
    return compiled.as_text()


def test_pallas_onehot_kernel_q1_shape(S):
    """(n, R, P) as q1 at SF=1 produces it: 9 live flags + 5 f64 sums as
    (hi, lo) pairs = 19 rows, 12 slots."""
    from ballista_tpu.ops import pallas_agg

    text = _compile(
        pallas_agg._program(N, 19, 12), S((1, N), "int32"), S((19, N), "float32")
    )
    assert "tpu_custom_call" in text


def test_q1_dense_aggregate_lowers_through_the_kernel(S, monkeypatch):
    from ballista_tpu.ops import aggregate as A
    from ballista_tpu.ops import pallas_agg
    from ballista_tpu.ops.aggregate import AggOp

    # the TPU branch: jax.default_backend() is "cpu" in this process
    monkeypatch.setattr(pallas_agg, "available", lambda: True)
    ops = (AggOp.SUM,) * 4 + (
        AggOp.COUNT, AggOp.COUNT, AggOp.SUM, AggOp.COUNT, AggOp.COUNT,
    )

    def q1_partial(codes, valid, vals):
        return A._dense_agg(
            list(codes), [None, None], (3, 2), valid, list(vals),
            [None] * 9, ops,
        )

    text = _compile(
        q1_partial,
        (S((N,), "int32"), S((N,), "int32")),
        S((N,), "bool"),
        tuple(S((N,), "float64") for _ in range(9)),
    )
    assert "tpu_custom_call" in text
    # occupancy is the kernel's count of rows a slot, not a scatter-set
    assert " scatter(" not in text


def test_g1q2_dense_aggregate_reduces_without_a_scatter(S):
    """g1q2's partial pass in ``h2o-g1-1e7-mem.groupby``: a 2M-row batch,
    two string keys of 100 values (10,201 slots), ``SUM(v1)`` of an int64.
    Counts, the sum and the occupancy go through the factorized one-hot
    (``ops/aggregate.py _factored_sums``): no scatter is left."""
    from ballista_tpu.ops import aggregate as A
    from ballista_tpu.ops.aggregate import AggOp

    n = 2 * N
    assert A.dense_factored(A.dense_slots((100, 100)))

    def g1q2_partial(codes, valid, v1):
        res = A._dense_agg(list(codes), [None, None], (100, 100), valid,
                           [v1], [None], (AggOp.SUM,))
        return res.values, res.value_nulls, res.valid, res.n_groups

    text = _compile(
        jax.jit(g1q2_partial),
        (S((n,), "int32"), S((n,), "int32")), S((n,), "bool"),
        S((n,), "int64"),
    )
    assert " scatter(" not in text
    assert " convolution(" in text or " dot(" in text


def test_float_prefix_takes_the_matmul_branch(S):
    from ballista_tpu.ops import aggregate as A

    _compile(
        lambda x: A._mm_prefix(x, A._PREFIX_BLOCK), S((2 * N, 2), "float64")
    )


def test_searchsorted_sort_method(S):
    """ops/search.py picks method='sort' on accelerators."""
    _compile(
        lambda a, v: jnp.searchsorted(a, v, method="sort"),
        S((4096,), "int64"), S((4096,), "int64"),
    )


@pytest.mark.parametrize("dtype", ["int64", "float64"])
def test_argsort_pass_program(S, dtype):
    from ballista_tpu.ops import perm

    prog = perm._argsort_program(dtype, 8192, True)
    assert " sort(" in _compile(prog, S((8192,), dtype))


@pytest.mark.parametrize("descending", [False, True])
def test_int64_key_halves_program_at_a_join_build_capacity(S, descending):
    """``ops/perm.py split_wide_ints``: the elementwise program that makes
    two int32 keys of an int64 key, at the 2M rows of q4's join build. It
    holds no sort: the halves ride the int32 argsort program."""
    from ballista_tpu.ops import perm

    n = 1 << 21
    text = _compile(perm._i64_keys_program(n, descending), S((n,), "int64"))
    assert " sort(" not in text


def test_stacked_gather_and_scatter_at_lineitem_capacity(S):
    from ballista_tpu.ops import aggregate as A
    from ballista_tpu.ops import perm

    n = 2 * N
    cols = (
        S((n,), "int64"), S((n,), "int64"), S((n,), "float64"),
        S((n,), "float64"), S((n,), "int32"),
    )
    _compile(
        lambda c, valid, p: perm.take_many_split([valid] + list(c), [], p),
        cols, S((n,), "bool"), S((n,), "int32"),
    )
    _compile(
        lambda rid, c: A._stacked_scatter_set(rid, 1 << 17, list(c)),
        S((n,), "int32"), tuple(S((n,), "float64") for _ in range(4)),
    )


def test_hash_partition_ids(S):
    from ballista_tpu.ops import partition

    n = 2 * N
    _compile(
        lambda a, b, valid: partition.partition_ids_for(
            [a, b], [None, None], valid, 2
        ),
        S((n,), "int64"), S((n,), "int32"), S((n,), "bool"),
    )


@pytest.fixture
def tpu_branches(monkeypatch):
    """Code that asks ``jax.default_backend()`` sees the CPU in this process
    and would take its CPU branch (an f64 ``cumsum``, which alone costs the
    TPU compiler two minutes); steer it here, in the test."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_filter_projection_sorted_aggregate_chain(S, tpu_branches):
    """``__graft_entry__.entry()``'s step: filter, projected measures and the
    sort-based grouped aggregate as one program."""
    from ballista_tpu.ops.aggregate import AggOp, group_aggregate

    def step(flag, qty, price, disc, valid):
        live = valid & (disc >= 0.02) & (disc <= 0.09)
        res = group_aggregate(
            [flag], [None], live, [qty, price, price * (1.0 - disc), qty],
            [None] * 4, [AggOp.SUM, AggOp.SUM, AggOp.SUM, AggOp.COUNT],
            capacity=64,
        )
        return res.keys[0], res.values[0], res.values[2], res.n_groups

    n = 4096
    text = _compile(
        step, S((n,), "int32"), S((n,), "float64"), S((n,), "float64"),
        S((n,), "float64"), S((n,), "bool"),
    )
    assert " sort(" in text
    # integers take cumsums of short blocks (ops/aggregate._blocked_prefix);
    # a float one costs this compiler two minutes
    assert not any("cumsum" in line and "f64[" in line
                   for line in text.splitlines())


def test_mesh_aggregate_stage_on_four_chips(topo, tpu_branches):
    """One whole stage program of parallel/stage.py (partial aggregate,
    hash exchange, final merge) on a 4-device mesh of the described chips:
    it compiles, and the all-to-all is in it."""
    import types

    from ballista_tpu.ops.aggregate import AggOp
    from ballista_tpu.parallel.mesh import SHARD_AXIS
    from ballista_tpu.parallel.stage import MeshStageRunner

    mesh = Mesh(np.array(topo.devices), (SHARD_AXIS,))
    rows = NamedSharding(mesh, PartitionSpec(SHARD_AXIS))
    n = 4 * 4096

    def arg(dt):
        return jax.ShapeDtypeStruct((n,), jnp.dtype(dt), sharding=rows)

    cols = (arg("int32"), arg("int32"), arg("float64"), arg("float64"))
    nulls = (None,) * 4
    prog = MeshStageRunner(mesh)._compile_aggregate(
        types.SimpleNamespace(columns=cols, nulls=nulls),
        (0, 1), (2, 3, 2), (AggOp.SUM, AggOp.SUM, AggOp.COUNT), 2048, 2048,
    )
    assert "all-to-all" in _compile(prog, cols, nulls, arg("bool"))


def test_group_by_segment_programs_at_the_h2o_cell_shape(S, tpu_branches):
    """The two programs of the sort path's segment reduction as one 2M-row
    batch of ``h2o-g1-1e7-mem.groupby``'s g1q3 reaches them (an int32 key, an
    int32 and a float64 sum, a count; 131,072 slots): integer prefixes by
    blocks and float totals in two levels, each a few seconds to compile
    where the stock cumsums and the one-column einsum cost this compiler 124 s
    (PERF.md, PR 29)."""
    from ballista_tpu.ops import aggregate as A
    from ballista_tpu.ops.aggregate import AggOp

    n, cap = 2 * N, 1 << 17
    ops = (AggOp.SUM, AggOp.SUM, AggOp.COUNT)
    dtypes = ("int32", "float64", "float64")
    layouts = A._seg_layouts(dtypes, (False,) * 3, ops)

    def part1(valid, key, vals, perm):
        return A._seg_part1(valid, [key], [None], list(vals), [None] * 3,
                            perm, ops, cap, False, *layouts)

    args = (S((n,), "bool"), S((n,), "int32"),
            tuple(S((n,), d) for d in dtypes), S((n,), "int32"))
    text = _compile(part1, *args)
    assert not any("cumsum" in line and "f64[" in line
                   for line in text.splitlines())
    n_groups, _, _, _, ps, cnt_cs, sum_cs, mm_vals = jax.eval_shape(part1, *args)
    floats = [cs for cs in sum_cs if isinstance(cs, tuple)]
    assert len(floats) == 1 and [a.shape for a in floats[0]] == [
        (n, 1), (n // 512 + 1, 1), (n // 512 + 1, 1)]

    def part2(n_groups, ps, cnt_cs, sum_cs, key):
        res = A._seg_part2(n_groups, ps, cnt_cs, list(sum_cs), [], [key],
                           [None], ops, cap, *layouts)
        return res.keys, res.values, res.valid

    def place(x):
        return jax.tree.map(lambda a: S(a.shape, a.dtype), x)

    _compile(part2, place(n_groups), place(ps), place(cnt_cs),
             place(tuple(sum_cs)), S((n,), "int32"))


# what one bucket of the exchange holds of h2o-g1-1e7-adv-mem's 1e7 rows
# (5e6) rounds up to on the capacity ladder
HOLISTIC_CAP = 1 << 23


@pytest.mark.parametrize("program", ["window_rank", "percentile_interp"])
def test_holistic_programs_at_the_h2o_adv_cell_shape(S, tpu_branches, program):
    """g1q8's ranking (``row_number`` over an int64 partition key, ordered by
    a float64) and g1q6's interpolation (two int64 keys with their null
    flags, a float64) as a task of ``h2o-g1-1e7-adv-mem.advanced`` reaches
    them: they compile in seconds, and the running count goes by 2048-row
    blocks, no stock ``cumsum`` over the whole length left (``row_number``
    counts nothing: its case holds the compile). The running maxima stay
    stock: the compiler blocks a one-column ``cummax`` itself (PERF.md,
    PR 33)."""
    n = HOLISTIC_CAP
    if program == "window_rank":
        from ballista_tpu.exec.window import _rank_program

        prog = _rank_program((False,), (False,), "row_number", n)
        args = ([S((n,), "int64")], [None], [S((n,), "float64")], [None],
                S((n,), "int32"))
    else:
        from ballista_tpu.exec.percentile import _pct_program

        prog = _pct_program((False,) * 4, False, (0.5,), n)
        keys = [S((n,), "int64"), S((n,), "bool")] * 2
        args = (keys, [None] * 4, S((n,), "float64"), None, S((n,), "bool"))
    _compile(prog, *args)
    lowered = prog.lower(*args).as_text()
    sums = [line for line in lowered.splitlines() if "call @cumsum" in line]
    assert bool(sums) == (program == "percentile_interp")
    whole = [line for line in sums
             if f"tensor<{n}xi64>" in line or f"tensor<{n}xi32>" in line]
    assert not whole, whole[0]


@pytest.mark.parametrize("dtype", ["int32", "int64"])
def test_integer_prefix_by_blocks(S, tpu_branches, dtype):
    from ballista_tpu.ops import aggregate as A

    _compile(A._prefix_sum_2d, S((1 << 19, 2), dtype))
