"""The one-pass GLM kernel, the sparse step's hot lookup (PR 30), the
Lloyd iteration's one-read kernel (PR 32), the ragged table's step in
width classes (PR 34) and that step split by frequency (PR 43),
compiled for a TPU v5e that is described, not attached: what the chip's compiler (Mosaic, XLA:TPU) accepts, which layout it
gives the slab, and that the kernel's view of the slab copies nothing.  No
chip time, about two seconds a compile.  Nothing runs, so nothing here is a
time or a result; the chip runs are ``chip_smoke.py`` and the benchmark.

The topology is described inside a fixture, never while a module is imported
(one process at a time may load libtpu: under several test workers only the
one that is given this file does), and the file skips where it cannot be.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flink_ml_tpu.lib import common
from flink_ml_tpu.lib.classification import _log_loss_grads
from flink_ml_tpu.ops import pallas_kernels

ROWS = 32768


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def quiet_cache():
    """A compile for a described chip is written to the persistent cache and
    cannot be read back without a chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def compiled_fit(topo, n_dev, steps, rows, d, onepass, monkeypatch):
    """The fused fit's program (unbundled: the bundle is float64 under the
    suite's x64) for ``n_dev`` described chips, with or without the kernel."""
    # the program asks which lowering the launch takes; here it is Mosaic
    monkeypatch.setattr(pallas_kernels, "launch_interpreted", lambda: False)
    mesh = Mesh(np.array(topo.devices[:n_dev]), ("data",))
    fn = common.make_glm_train_fn(
        _log_loss_grads(True), mesh, 0.1, 0.0, 10, 0.0,
        onepass_rows=pallas_kernels.glm_grad_tile(rows, d) if onepass else 0)
    replicated = NamedSharding(mesh, P())
    args = ((jax.ShapeDtypeStruct((d,), jnp.float32, sharding=replicated),
             jax.ShapeDtypeStruct((), jnp.float32, sharding=replicated)),
            jax.ShapeDtypeStruct((n_dev * steps, rows, d + 2), jnp.float32,
                                 sharding=NamedSharding(mesh, P("data"))))
    return fn.lower(*args).compile()


def slab_layout(text):
    return re.search(r"entry_computation_layout=\{\(.*?f32\[\d+,\d+,\d+\]"
                     r"(\{[^}]*\})", text).group(1)


@pytest.mark.parametrize("steps,d", [(13, 2000), (62, 784), (7, 28), (7, 37)],
                         ids=["epsilon", "mnist8m", "narrow", "odd-width"])
def test_the_kernel_compiles_and_reads_the_slab_in_place(
        topo, quiet_cache, monkeypatch, steps, d):
    compiled = compiled_fit(topo, 1, steps, ROWS, d, True, monkeypatch)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # rows minor, features next, steps major: the kernel's view is a bitcast
    assert slab_layout(text) == "{1,2,0:T(8,128)}"
    minibatch = ROWS * (d + 2) * 4
    assert compiled.memory_analysis().temp_size_in_bytes < minibatch // 8
    # no slice of the slab: the scan's one slice a step is the schedule's
    # row, the kernel's (2,) int32 operand
    assert not re.search(r"%dynamic-slice_bitcast_fusion\S* = f32", text)
    assert len(re.findall(r"%dynamic-slice_bitcast_fusion\S* = s32\[2\]",
                          text)) <= 1


def test_two_tables_of_one_slab_shape_build_one_program(topo, quiet_cache,
                                                         monkeypatch):
    """The row tiles a step reads are counted from the slab's weight row in
    the program: two tables of other row counts packed to one slab shape
    share the cache key, the program letter for letter and, fitted on the
    CPU's interpreter, the one compile."""
    from flink_ml_tpu import obs

    d, tables = 12, []
    for n in (3 * 512 - 40, 3 * 512 - 400):
        rng = np.random.RandomState(n)
        X = rng.randn(n, d).astype(np.float32)
        y = (X @ rng.randn(d) > 0).astype(np.float64)
        tables.append((n, common._combined_view(
            common.pack_minibatches(X, y, 1, 512))))
    assert tables[0][1].shape == tables[1][1].shape == (3, 512, d + 2)
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    monkeypatch.setattr(pallas_kernels, "launch_interpreted", lambda: False)
    texts = []
    for _n, slab in tables:
        fn = common.make_glm_train_fn(_log_loss_grads(True), mesh, 0.1, 0.0,
                                      10, 0.0, onepass_rows=128)
        texts.append((fn, fn.lower(
            (jax.ShapeDtypeStruct((d,), jnp.float32,
                                  sharding=NamedSharding(mesh, P())),
             jax.ShapeDtypeStruct((), jnp.float32,
                                  sharding=NamedSharding(mesh, P()))),
            jax.ShapeDtypeStruct(slab.shape, slab.dtype,
                                 sharding=NamedSharding(mesh, P("data"))),
        ).as_text()))
    assert texts[0][0] is texts[1][0]
    assert texts[0][1] == texts[1][1]
    monkeypatch.undo()

    from flink_ml_tpu.parallel.mesh import default_mesh

    cpu = Mesh(np.array(default_mesh().devices.flat[:1]), ("data",))
    obs.enable()
    obs.reset()
    try:
        for n, slab in tables:
            fn = common.make_glm_train_fn(_log_loss_grads(True), cpu, 0.1,
                                          0.0, 2, 0.0, bundle=True,
                                          onepass_rows=128)
            p0 = (jnp.zeros((d,), jnp.float32), jnp.zeros((), jnp.float32))
            common._run_fused_train(fn, p0, slab, cpu, n_rows=n)
        counters = obs.registry().snapshot()["counters"]
    finally:
        obs.disable()
        obs.reset()
    jitted = fn.__closure__[0].cell_contents
    assert jitted._cache_size() == 1
    assert counters["train.fused_runs"] == 2
    assert counters.get("train.compile_runs", 0) <= 1
    # two epochs each: the first table's last step holds 472 rows (all 4
    # tiles read), the second's 112 (1 read, 3 passed over)
    assert counters["train.onepass_tiles_skipped"] == 2 * (0 + 3)
    assert counters["train.onepass_tiles"] == 2 * (12 + 9)


def test_the_xla_step_it_replaces_copies_a_minibatch(topo, quiet_cache,
                                                     monkeypatch):
    """The parent's program at epsilon's shape, for the comparison: the
    scan's slice is a copy of the minibatch (PERF.md §5, bottleneck 1)."""
    compiled = compiled_fit(topo, 1, 13, ROWS, 2000, False, monkeypatch)
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes >= ROWS * 2002 * 4


def test_four_chips_compile_under_strict_vma(topo, quiet_cache, monkeypatch):
    """Each chip runs the kernel on its own rows, the psum after it."""
    compiled = compiled_fit(topo, 4, 13, 8192, 2000, True, monkeypatch)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert len(re.findall(r"= .* all-reduce\(", text)) >= 1
    assert slab_layout(text) == "{1,2,0:T(8,128)}"


def test_the_four_chip_cell_compiles_with_one_all_reduce_a_step(
        topo, quiet_cache, monkeypatch):
    """``mnist8m_lr_dp4.sweep``'s own program (PR 37): 62 steps of 32768 rows
    a chip over a 2x2 host.  The kernel is in it, every chip holds its 6.39
    GB of the slab where it lies, and the compiler joins the step's four
    ``psum``s (weights, intercept, loss, count) into ONE all-reduce, named
    under the program's ``fmt.train.psum`` scope."""
    compiled = compiled_fit(topo, 4, 62, ROWS, 784, True, monkeypatch)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert slab_layout(text) == "{1,2,0:T(8,128)}"
    reduces = re.findall(r"= .* all-reduce\(.*", text)
    assert len(reduces) == 1
    assert "f32[784]" in reduces[0] and "replica_groups={{0,1,2,3}}" in \
        reduces[0]
    assert "fmt.train.grad/fmt.train.psum" in reduces[0]
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes >= 62 * ROWS * 786 * 4  # a chip's
    assert memory.temp_size_in_bytes < 1 << 20


def test_a_sharded_slabs_slices_are_joined_with_no_collective(topo,
                                                              quiet_cache):
    """The placement's reassembly over four chips (PR 37): every device
    joins the slices it was sent, into the layout the kernel reads."""
    from flink_ml_tpu.parallel.mesh import _concat_placed_fn

    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    part = jax.ShapeDtypeStruct((4, ROWS, 786), jnp.float32,
                                sharding=NamedSharding(mesh, P("data")))
    compiled = _concat_placed_fn(mesh, P("data"), 62).lower(
        *[part] * 62).compile()
    text = compiled.as_text()
    for collective in ("all-to-all", "collective-permute", "all-gather",
                       "all-reduce"):
        assert collective not in text
    assert re.search(r"->\s*\(?f32\[62,32768,786\](\{[^}]*\})",
                     text).group(1) == "{1,2,0:T(8,128)}"


@pytest.mark.parametrize("shape,layout", [
    ((8, ROWS, 2002), "{1,0,2:T(8,128)}"),    # steps take the sublanes
    ((62, ROWS, 30), "{1,0,2:T(8,128)}"),     # chip_smoke's HIGGS shape
    ((13, ROWS, 128), "{2,1,0:T(8,128)}"),    # features fill the lanes
    ((13, 1000, 2002), "{2,1,0:T(8,128)}"),   # rows do not
])
def test_the_chip_lays_other_shapes_otherwise(topo, quiet_cache, shape,
                                              layout):
    """Why the selection rule reads the placed slab's layout and does not
    reckon it: the chip picks it from the shape alone, by padding."""
    from jax.sharding import SingleDeviceSharding

    x = jax.ShapeDtypeStruct(shape, jnp.float32,
                             sharding=SingleDeviceSharding(topo.devices[0]))
    text = jax.jit(lambda a: a * 2.0).lower(x).compile().as_text()
    assert slab_layout(text) == layout


# -- the sparse step's frequency split (PR 30) ---------------------------------


def lowered_split_fit(topo, n_dev, steps, mb, width, dim, cold_slots, k,
                      monkeypatch):
    """The split sparse fit's program (unbundled, as above) lowered for
    ``n_dev`` described chips from the shapes of its six leaves alone."""
    monkeypatch.setattr(pallas_kernels, "launch_interpreted", lambda: False)
    mesh = Mesh(np.array(topo.devices[:n_dev]), ("data",))
    step = common.make_hot_ell_grad_step("logistic", mb, width, dim, True,
                                         interpret=False)
    fn = common._build_fused_train_fn(
        ("aot-sparse-ell-hot", n_dev, steps, mb, width, dim, cold_slots, k,
         mesh), None, mesh, 0.1, 0.0, 1, 0.0, whole_batch_step=step)
    replicated = NamedSharding(mesh, P())
    sharded = NamedSharding(mesh, P("data"))

    def leaf(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharded)

    blocks = n_dev * steps
    args = ((jax.ShapeDtypeStruct((dim,), jnp.float32, sharding=replicated),
             jax.ShapeDtypeStruct((), jnp.float32, sharding=replicated)),
            (leaf((blocks, width, mb), jnp.int32),
             leaf((blocks, width + 2, mb), jnp.float32),
             leaf((blocks, cold_slots), jnp.int32),
             leaf((blocks, cold_slots), jnp.float32),
             leaf((blocks, 2, width), jnp.int32),
             leaf((n_dev, k), jnp.int32)))
    return fn.lower(*args)


#: the sparse cell's cold list: its fullest step's 116,2xx-117,2xx cold
#: entries (by seed) rounded up to an odd multiple of 512
COLD_SLOTS = 117248


@pytest.mark.parametrize("n_dev,mb,k", [(1, ROWS, 16384), (1, ROWS, 4096),
                                        (4, ROWS // 4, 16384)],
                         ids=["criteo", "criteo-4096", "four-chips"])
def test_the_split_sparse_step_compiles_with_both_hot_kernels(
        topo, quiet_cache, monkeypatch, n_dev, mb, k):
    assert COLD_SLOTS == common.padded_nnz(116_300, 512) \
        == common.padded_nnz(117_200, 512)
    text = lowered_split_fit(topo, n_dev, 8, mb, 39, 1_000_000, COLD_SLOTS,
                             k, monkeypatch).compile().as_text()
    # hot_scores and hot_grad, under strict check_vma on four chips too
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "hot_scores" in text and "hot_grad" in text
    if n_dev > 1:
        assert len(re.findall(r"= .* all-reduce\(", text)) >= 1


def test_the_split_step_keeps_one_gather_and_one_scatter_over_the_cold_slots(
        topo, quiet_cache, monkeypatch):
    """Beside the two kernels the chip's compiler keeps ONE gather and ONE
    scatter over the cold slots (and the hot weights' take and the scatter
    of their sums, 16384 long): no sum by row id, no take of the error; the
    39 planes' slices of the products sit in one fusion, and the error's
    39 writes update the cold buffer where it lies."""
    mb, width, k = ROWS, 39, 16384
    text = lowered_split_fit(topo, 1, 8, mb, width, 1_000_000, COLD_SLOTS, k,
                             monkeypatch).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    gathers = re.findall(r"= f32\[(\d+)\]\S* gather\(", text)
    assert sorted(gathers) == sorted([str(COLD_SLOTS), str(k)])
    scatters = re.findall(r"= f32\[(\d+)\]\S* scatter\(", text)
    assert scatters == ["1000000", "1000000"]  # the cold slots', the hot sums'
    assert "indices_are_sorted=true" not in text
    assert "fmt.train.sparse.take_weights" in text
    assert "fmt.train.sparse.scatter" in text
    assert "fmt.train.sparse.row_sum" not in text
    assert "fmt.train.sparse.take_error" not in text
    # the error's way to the slots: a write a plane into ONE buffer of
    # cold_slots + mb, each on the one before, and no copy of it
    buffer = rf"f32\[{COLD_SLOTS + mb}\]"
    writes = re.findall(rf"= {buffer}\S* dynamic-update-slice\(", text)
    assert len(writes) == width
    assert not re.findall(rf"= {buffer}\S* copy\(", text)
    assert len(re.findall(rf"= f32\[{mb}\]\S* dynamic-slice\(", text)) \
        >= width


def _split_stack(seed, skew):
    """A small table of the sparse cell's kind split by frequency: every
    row 5 wide, a power law over 3000 features, 256 of them hot."""
    rng = np.random.RandomState(seed)
    rows, dim, width = 512, 3000, 5
    ids = np.where(rng.rand(rows * width) < 0.8,
                   (rng.zipf(skew, rows * width) - 1) * 7919 % dim,
                   rng.randint(0, dim, rows * width)).astype(np.int32)
    indptr = width * np.arange(rows + 1, dtype=np.int64)
    values = rng.randn(rows * width).astype(np.float32)
    y = (rng.rand(rows) < 0.35).astype(np.float64)
    counts = np.bincount(ids, minlength=dim)
    hot_ids = np.argsort(-counts, kind="stable")[:256].astype(np.int32)
    from flink_ml_tpu.ops.batch import CsrRows

    bounds = [(lo, lo + 128, lo * width, (lo + 128) * width)
              for lo in range(0, rows, 128)]
    return common._pack_ell_split(CsrRows(dim, indptr, ids, values), y,
                                  bounds, np.diff(indptr), width, 128, 4,
                                  dim, 1, 512, hot_ids)


def test_two_tables_of_one_shape_lower_to_the_same_program(
        topo, quiet_cache, monkeypatch):
    """The cold planes' starts and lengths are data: two tables of one
    shape whose rows draw other cold widths give ONE program, letter for
    letter, so the second finds the first's in the compile cache (the
    ragged cell's width classes are constants of its program and it has no
    warm state: PERF.md, section 5, the set-up account)."""
    one, other = _split_stack(1, 1.4), _split_stack(2, 1.1)
    assert one.cold_slots == other.cold_slots
    assert [a.shape for a in one.batch] == [a.shape for a in other.batch]
    # other cold widths: other planes, other cuts, another order of rows
    assert not np.array_equal(one.cold_cuts, other.cold_cuts)
    assert not np.array_equal(one.cold_cuts[:, 1].max(axis=0) > 0,
                              other.cold_cuts[:, 1].max(axis=0) > 0)
    texts = []
    for stack in (one, other):
        key, _step = stack.grad_step("logistic")
        assert key == ("sparse-ell-hot", 128, 5, 3000, one.cold_slots, 256)
        common._EPOCH_STEP_CACHE.clear()
        texts.append(lowered_split_fit(
            topo, 1, 4, stack.mb, stack.width, stack.dim, stack.cold_slots,
            256, monkeypatch).as_text())
    assert texts[0] == texts[1]
    assert texts[0].count("dynamic_update_slice") >= 5


# -- the ragged table in width classes (PR 34) ---------------------------------

#: the cut of the ragged cell's table at seed 3405000003 (my chip run, PR 34)
URL_CLASSES = (
    (128, 472), (128, 264), (256, 243), (256, 223), (384, 211), (512, 200),
    (640, 189), (512, 180), (512, 174), (768, 169), (896, 163), (1280, 157),
    (1024, 150), (1408, 145), (1408, 139), (1152, 134), (1280, 130),
    (1408, 126), (1536, 122), (1408, 118), (1536, 114), (1536, 110),
    (1536, 106), (1664, 102), (1792, 98), (1408, 93), (1536, 89), (1408, 84),
    (1152, 79), (896, 74), (768, 69), (640, 62))


@pytest.mark.parametrize("n_dev", [1, 4], ids=["url", "four-chips"])
def test_the_classed_sparse_step_keeps_one_gather_and_one_scatter(
        topo, quiet_cache, n_dev):
    """At the ragged cell's shapes the chip's compiler keeps ONE gather and
    ONE scatter over all of a step's slots, whatever the number of classes:
    the concatenations become slice updates in place."""
    mb, dim, steps = ROWS, 3_231_961, 74 // n_dev
    mesh = Mesh(np.array(topo.devices[:n_dev]), ("data",))
    slots = common.padded_nnz(sum(r * w for r, w in URL_CLASSES), 512)
    assert slots == 4_069_888 and (slots // 512) % 2 == 1
    step = common.make_classed_ell_grad_step("logistic", mb, URL_CLASSES,
                                             slots, dim, True)
    fn = common._build_fused_train_fn(
        ("aot-sparse-ell-classed", n_dev, mesh), step, mesh, 0.1, 1e-4, 1,
        0.0)
    replicated = NamedSharding(mesh, P())
    sharded = NamedSharding(mesh, P("data"))
    blocks = n_dev * steps
    args = ((jax.ShapeDtypeStruct((dim,), jnp.float32, sharding=replicated),
             jax.ShapeDtypeStruct((), jnp.float32, sharding=replicated)),
            (jax.ShapeDtypeStruct((blocks, slots + 2 * mb), jnp.int32,
                                  sharding=sharded),
             jax.ShapeDtypeStruct((blocks, slots + 2 * mb), jnp.float32,
                                  sharding=sharded)))
    text = fn.lower(*args).compile().as_text()
    assert len(re.findall(rf"f32\[{slots}\]\S* gather\(", text)) == 1
    assert len(re.findall(r" gather\(", text)) == 3  # and two of mb rows
    assert len(re.findall(r" scatter\(", text)) == 1
    assert "fmt.train.sparse.take_weights" in text
    assert "fmt.train.sparse.scatter" in text
    if n_dev > 1:
        assert len(re.findall(r"= .* all-reduce\(", text)) >= 1


# -- the ragged table's classed step split by frequency (PR 43) ----------------

#: the cell's split leaves at seed 3405000003 on one chip and a quarter of a
#: step on each of four: (mb, steps, hot blocks, cold slots, cold planes)
URL_SPLIT = {1: (ROWS, 74, 432, 773_632, 128),
             4: (ROWS // 4, 19, 112, 196_096, 128)}
URL_DIM = 3_231_961


def lowered_classed_split_fit(topo, n_dev, mb, steps, nb, cold_slots,
                              planes, dim, k, monkeypatch):
    """The classed split's fused fit (unbundled, as above) lowered for
    ``n_dev`` described chips from the shapes of its nine leaves alone."""
    monkeypatch.setattr(pallas_kernels, "launch_interpreted", lambda: False)
    mesh = Mesh(np.array(topo.devices[:n_dev]), ("data",))
    step = common.make_classed_hot_grad_step("logistic", mb, dim, True,
                                             interpret=False)
    tile = common._hot_block_tile(mb)
    fn = common._build_fused_train_fn(
        ("aot-sparse-ell-classed-hot", n_dev, steps, mb, nb, cold_slots,
         planes, dim, k, mesh), None, mesh, 0.1, 1e-4, 1, 0.0,
        whole_batch_step=step)
    replicated = NamedSharding(mesh, P())
    sharded = NamedSharding(mesh, P("data"))

    def leaf(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharded)

    blocks = n_dev * steps
    hot = (blocks, nb, common._HOT_BLOCK_PLANES, tile)
    args = ((jax.ShapeDtypeStruct((dim,), jnp.float32, sharding=replicated),
             jax.ShapeDtypeStruct((), jnp.float32, sharding=replicated)),
            (leaf(hot, jnp.int32), leaf(hot, jnp.float32),
             leaf((blocks, 2, nb), jnp.int32),
             leaf((blocks, cold_slots), jnp.int32),
             leaf((blocks, cold_slots), jnp.float32),
             leaf((blocks, 2, planes), jnp.int32),
             leaf((blocks, 4, mb), jnp.int32),
             leaf((blocks, 2, mb), jnp.float32),
             leaf((n_dev, k), jnp.int32)))
    return fn.lower(*args)


@pytest.mark.parametrize("n_dev", [1, 4], ids=["url", "four-chips"])
def test_the_classed_split_step_compiles_with_one_call_a_direction(
        topo, quiet_cache, monkeypatch, n_dev):
    """At the ragged cell's shapes: ONE ``hot_scores`` and ONE ``hot_grad``
    whatever the widths, ONE gather and ONE scatter over the cold list (and
    the hot weights' take, the scatter of their sums, four takes of ``mb``),
    the planes in loops: one slice of the products and one write of the
    error in the program, not one a plane; the hot blocks read where they
    lie (no slice of a step's blocks is made)."""
    mb, steps, nb, cold_slots, planes = URL_SPLIT[n_dev]
    assert cold_slots == common.padded_nnz(cold_slots - 600, 512)
    k = 16384
    compiled = lowered_classed_split_fit(
        topo, n_dev, mb, steps, nb, cold_slots, planes, URL_DIM, k,
        monkeypatch).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "hot_scores" in text and "hot_grad" in text
    gathers = re.findall(r"= f32\[(\d+)\]\S* gather\(", text)
    assert sorted(gathers) == sorted([str(cold_slots), str(k)]
                                     + [str(mb)] * 4)
    scatters = re.findall(r"= f32\[(\d+)\]\S* scatter\(", text)
    assert scatters == [str(URL_DIM)] * 2  # the cold slots', the hot sums'
    assert "fmt.train.sparse.take_weights" in text
    assert "fmt.train.sparse.scatter" in text
    assert "fmt.train.sparse.row_sum" not in text
    buffer = rf"f32\[{cold_slots + mb}\]"
    assert len(re.findall(rf"= {buffer}\S* dynamic-update-slice\(", text)) \
        == 1
    assert not re.findall(rf"= {buffer}\S* copy\(", text)
    assert len(re.findall(rf"= f32\[{mb}\]\S* dynamic-slice\(", text)) == 1
    hot_leaf = nb * common._HOT_BLOCK_PLANES * \
        common._hot_block_tile(mb) * 4  # a step's codes
    assert compiled.memory_analysis().temp_size_in_bytes < hot_leaf
    if n_dev > 1:
        assert len(re.findall(r"= .* all-reduce\(", text)) >= 1


def _classed_split_stack(seed, zipf, width_max, monkeypatch):
    """A small ragged table split by frequency in width classes: 2048 rows
    of 8 to ``width_max`` entries, a power law over 3000 features, 256 of
    them hot, one device's steps of 1024 rows."""
    from flink_ml_tpu.ops.batch import CsrRows

    rng = np.random.RandomState(seed)
    rows, dim = 2048, 3000
    widths = np.minimum(width_max, 8 + rng.geometric(0.08, rows))
    indptr = np.concatenate([[0], np.cumsum(widths)]).astype(np.int64)
    n = int(indptr[-1])
    ids = np.where(rng.rand(n) < 0.8, (rng.zipf(zipf, n) - 1) * 7919 % dim,
                   rng.randint(0, dim, n)).astype(np.int32)
    values = rng.randn(n).astype(np.float32)
    y = (rng.rand(rows) < 0.35).astype(np.float64)
    monkeypatch.setattr(common, "_HOT_K", 256)
    monkeypatch.setattr(common, "_hot_split_measured", lambda: True)
    monkeypatch.setattr(common, "_hot_split_wins", lambda *a: True)
    stack = common.pack_sparse_minibatches(
        CsrRows(dim, indptr, ids, values), y, 1, 1024, dim=dim,
        row_regular=True)
    assert type(stack) is common.ClassedEllMinibatchStack
    assert stack.hot_ids is not None and stack.ell_classes > 1
    return stack


def test_two_classed_tables_of_one_shape_lower_to_the_same_program(
        topo, quiet_cache, monkeypatch):
    """The hot blocks' schedule and the cold planes' cuts are data: two
    tables of one shape whose rows draw other hot and cold widths (and
    other width classes) give ONE program, letter for letter."""
    one = _classed_split_stack(1, 1.4, 60, monkeypatch)
    other = _classed_split_stack(2, 1.45, 52, monkeypatch)
    assert [a.shape for a in one.batch] == [a.shape for a in other.batch]
    assert one.classes != other.classes
    assert not np.array_equal(one.hot_sched, other.hot_sched)
    assert not np.array_equal(one.cold_cuts[:, 1].max(axis=0) > 0,
                              other.cold_cuts[:, 1].max(axis=0) > 0)
    texts = []
    for stack in (one, other):
        key, _step = stack.grad_step("logistic")
        assert key == one.grad_step("logistic")[0]
        common._EPOCH_STEP_CACHE.clear()
        nb = stack.hot_codes.shape[1]
        texts.append(lowered_classed_split_fit(
            topo, 1, stack.mb, stack.steps, nb, stack.cold_slots,
            stack.cold_cuts.shape[-1], stack.dim, 256,
            monkeypatch).as_text())
    assert texts[0] == texts[1]


#: the operations of the one-width split's program (Criteo's) as PR 36 left
#: them, lowered for one described chip at the cell's shapes: the classed
#: split leaves them as they were
CRITEO_OPS = {
    "func.call": 1, "stablehlo.abs": 1, "stablehlo.add": 145,
    "stablehlo.all_reduce": 4, "stablehlo.and": 5,
    "stablehlo.bitcast_convert": 6, "stablehlo.broadcast_in_dim": 81,
    "stablehlo.compare": 133, "stablehlo.concatenate": 2,
    "stablehlo.constant": 265, "stablehlo.convert": 4,
    "stablehlo.custom_call": 2, "stablehlo.divide": 5,
    "stablehlo.dynamic_slice": 45, "stablehlo.dynamic_update_slice": 41,
    "stablehlo.exponential": 2, "stablehlo.gather": 2, "stablehlo.iota": 2,
    "stablehlo.log_plus_one": 1, "stablehlo.maximum": 3,
    "stablehlo.multiply": 13, "stablehlo.negate": 2, "stablehlo.pad": 1,
    "stablehlo.reduce": 9, "stablehlo.reshape": 138, "stablehlo.scatter": 3,
    "stablehlo.select": 91, "stablehlo.slice": 129, "stablehlo.sqrt": 1,
    "stablehlo.subtract": 10, "stablehlo.transpose": 3, "stablehlo.while": 2}


def test_the_one_width_split_lowers_to_the_operations_it_had(
        topo, quiet_cache, monkeypatch):
    import collections

    text = lowered_split_fit(topo, 1, 8, ROWS, 39, 1_000_000, COLD_SLOTS,
                             16384, monkeypatch).as_text()
    ops = collections.Counter(re.findall(r'= "?([a-z_]+\.[a-z_]+)', text))
    assert dict(ops) == CRITEO_OPS
    # its kernels are the one-width calls, not the blocks' own
    assert "hot_scores_blocks" not in text and "hot_grad_blocks" not in text


# -- the centroid fit (PR 31) --------------------------------------------------


def compiled_kmeans(topo, n_dev, rows, width, k=100, iters=20,
                    monkeypatch=None):
    """The fused Lloyd program (unbundled: the bundle is float64 under the
    suite's x64) for ``n_dev`` described chips, ``rows`` a chip; with a
    ``monkeypatch`` the one that holds the kernel (the route's rule asks the
    process's platform which lowering the launch takes; here it is Mosaic)."""
    from flink_ml_tpu.lib import clustering

    mesh = Mesh(np.array(topo.devices[:n_dev]), ("data",))
    kernel_rows = 0
    if monkeypatch is not None:
        monkeypatch.setattr(pallas_kernels, "launch_interpreted",
                            lambda: False)
        assert clustering._lloyd_kernel_platform(mesh)
        kernel_rows = pallas_kernels.lloyd_sums_tile(rows, width, k)
        assert kernel_rows
    fn = clustering.make_kmeans_train_fn(mesh, k, iters, 0.0, bundle=False,
                                         kernel_rows=kernel_rows)
    replicated = NamedSharding(mesh, P())
    sharded = NamedSharding(mesh, P("data"))
    args = ((jax.ShapeDtypeStruct((k, width), jnp.float32,
                                  sharding=replicated),
             jax.ShapeDtypeStruct((iters, k, width), jnp.float32,
                                  sharding=replicated)),
            (jax.ShapeDtypeStruct((n_dev * rows, width), jnp.float32,
                                  sharding=sharded),
             jax.ShapeDtypeStruct((n_dev * rows,), jnp.float32,
                                  sharding=sharded)))
    return fn.lower(*args).compile()


def table_layout(text):
    return re.search(r"entry_computation_layout=\{\(.*?f32\[\d+,\d+,\d+\]"
                     r"\{[^}]*\}, f32\[\d+,\d+\](\{[^}]*\})", text).group(1)


@pytest.mark.parametrize("n_dev,k", [(1, 100), (4, 100), (1, 256)],
                         ids=["one-chip", "four-chips", "two-lane-chunks"])
def test_the_lloyd_kernel_compiles_and_reads_the_table_in_place(
        topo, quiet_cache, monkeypatch, n_dev, k):
    """mnist8m's quarter a chip, packed 896 wide, through the one-read
    kernel (strict vma on four chips): Mosaic takes it, the table lies
    features-minor where the kernel's blocks read it, and no (rows, k)
    distances lie among the program's temporaries but those of the rows a
    whole number of tiles leaves."""
    rows, width = 2_025_000, 896
    compiled = compiled_kmeans(topo, n_dev, rows, width, k=k,
                               monkeypatch=monkeypatch)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "lloyd_sums" in text
    assert table_layout(text) == "{1,0:T(8,128)}"
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes >= rows * width * 4
    # the rows' squared norms (8.1 MB) and little else: far under 2^30
    assert memory.temp_size_in_bytes < 16 * rows
    left = rows % pallas_kernels.lloyd_sums_tile(rows, width, k)
    wide = {int(n) for n in re.findall(r"f32\[(\d+),(?:%d|128|256)\]" % k,
                                       text)}
    assert left in wide and max(wide) <= max(left, 3 * 256), wide
    if n_dev > 1:
        assert len(re.findall(r"= .* all-reduce\(", text)) >= 1


@pytest.mark.parametrize("n_dev", [1, 4], ids=["one-chip", "four-chips"])
def test_the_lloyd_program_reads_the_lane_aligned_table_where_it_lies(
        topo, quiet_cache, n_dev):
    """The XLA tiles (what a table the kernel declines runs).  mnist8m's
    quarter a chip, packed 896 wide: the table lies
    features-minor, as the distance product streams it, and the program
    holds no temporary near the table's size (a tile's worth)."""
    from flink_ml_tpu.lib import clustering

    width = clustering.packed_width(784)
    assert width == 896
    compiled = compiled_kmeans(topo, n_dev, 2_025_000, width)
    text = compiled.as_text()
    assert table_layout(text) == "{1,0:T(8,128)}"
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes >= 2_025_000 * 896 * 4
    assert memory.temp_size_in_bytes < 2**30  # 18 MB at a tile of 16384
    if n_dev > 1:
        assert len(re.findall(r"= .* all-reduce\(", text)) >= 1


def test_the_chip_lays_the_784_wide_table_rows_minor_and_the_program_copies_it(
        topo, quiet_cache):
    """Why the pack pads: left 784 wide the chip lays the table rows-minor
    (no padding that way) and the program opens with a copy of all of it
    into the padded features-minor layout, a temporary larger than the
    table."""
    compiled = compiled_kmeans(topo, 1, 2_025_000, 784)
    assert table_layout(compiled.as_text()) == "{0,1:T(8,128)}"
    assert compiled.memory_analysis().temp_size_in_bytes > \
        2_025_000 * 784 * 4
