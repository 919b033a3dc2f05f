"""Compile management (accelerate_tpu/aot): executable store round-trips,
cross-process warm start with zero XLA compiles, content-key invalidation,
poison rejection, shape bucketing, and the CompileKwargs/serving wiring."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.aot import (
    CorruptEntryError,
    ExecutableStore,
    ProgramCache,
    ShapeBucketer,
    StaleEntryError,
    content_key,
    deserialize_compiled,
    next_pow2,
    pad_batch_tree,
    resolve_cache_dir,
    serialize_compiled,
)
from accelerate_tpu.telemetry.eventlog import EventLog, read_events

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fn(x, w):
    return jnp.tanh(x @ w).sum()


def _avals():
    return (
        jax.ShapeDtypeStruct((8, 16), jnp.float32),
        jax.ShapeDtypeStruct((16, 16), jnp.float32),
    )


# --------------------------------------------------------------------- #
# store + round-trip
# --------------------------------------------------------------------- #


def test_serialize_roundtrip_bit_exact():
    """Serialized -> deserialized executable produces bit-identical
    outputs to the original compiled program."""
    lowered = jax.jit(_fn).lower(*_avals())
    compiled = lowered.compile()
    loaded = deserialize_compiled(serialize_compiled(compiled))
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 16)).astype(np.float32)
    w = rng.standard_normal((16, 16)).astype(np.float32)
    a, b = np.asarray(compiled(x, w)), np.asarray(loaded(x, w))
    np.testing.assert_array_equal(a, b)


def test_store_put_get_and_header(tmp_path):
    store = ExecutableStore(str(tmp_path))
    store.put("k" * 64, b"payload-bytes", name="demo")
    assert store.get("k" * 64) == b"payload-bytes"
    header = store.read_header("k" * 64)
    assert header["name"] == "demo" and header["size"] == len(b"payload-bytes")
    assert store.get("absent" * 8) is None
    assert store.keys() == ["k" * 64]


def test_store_rejects_poisoned_entry(tmp_path):
    store = ExecutableStore(str(tmp_path))
    store.put("k" * 64, b"payload-bytes", name="demo")
    path = store._entry_path("k" * 64)
    with open(path, "rb") as f:
        blob = f.read()
    with open(path, "wb") as f:
        f.write(blob[:-4] + b"XXXX")
    with pytest.raises(CorruptEntryError):
        store.get("k" * 64)


def test_store_rejects_stale_jax_version(tmp_path):
    """An entry whose header claims a different jax version must never
    deserialize — the stale-key invalidation the content key provides is
    double-checked at read time."""
    store = ExecutableStore(str(tmp_path))
    store.put("k" * 64, b"payload-bytes", name="demo")
    path = store._entry_path("k" * 64)
    with open(path, "rb") as f:
        magic, header, payload = f.readline(), json.loads(f.readline()), f.read()
    header["jax"] = "0.0.1-somethingelse"
    with open(path, "wb") as f:
        f.write(magic + json.dumps(header).encode() + b"\n" + payload)
    with pytest.raises(StaleEntryError):
        store.get("k" * 64)


def test_content_key_changes_with_shape_mesh_and_salt(mesh8):
    from jax.sharding import NamedSharding, PartitionSpec as P

    base = content_key(jax.jit(_fn).lower(*_avals()))
    other_shape = content_key(
        jax.jit(_fn).lower(
            jax.ShapeDtypeStruct((16, 16), jnp.float32), jax.ShapeDtypeStruct((16, 16), jnp.float32)
        )
    )
    sharded_aval = jax.ShapeDtypeStruct((8, 16), jnp.float32, sharding=NamedSharding(mesh8, P("data")))
    other_mesh = content_key(jax.jit(_fn).lower(sharded_aval, _avals()[1]))
    salted = content_key(jax.jit(_fn).lower(*_avals()), extra=("v2",))
    assert len({base, other_shape, other_mesh, salted}) == 4
    # and deterministic for identical input
    assert base == content_key(jax.jit(_fn).lower(*_avals()))


# --------------------------------------------------------------------- #
# ProgramCache
# --------------------------------------------------------------------- #


def test_program_cache_memory_then_disk_hit(tmp_path):
    pc = ProgramCache(store=ExecutableStore(str(tmp_path)))
    pc.compile(_fn, *_avals(), name="t")
    pc.compile(_fn, *_avals(), name="t")
    assert (pc.misses, pc.hits, pc.deserialized) == (1, 1, 0)

    fresh = ProgramCache(store=ExecutableStore(str(tmp_path)))
    compiled = fresh.compile(_fn, *_avals(), name="t")
    assert (fresh.misses, fresh.deserialized) == (0, 1)
    assert float(compiled(np.ones((8, 16), np.float32), np.ones((16, 16), np.float32))) == pytest.approx(
        float(jax.jit(_fn)(np.ones((8, 16), np.float32), np.ones((16, 16), np.float32)))
    )


def test_program_cache_rejects_and_heals_poison(tmp_path, tmp_path_factory):
    log_path = str(tmp_path_factory.mktemp("log") / "run.jsonl")
    pc = ProgramCache(store=ExecutableStore(str(tmp_path)))
    pc.compile(_fn, *_avals(), name="t")
    key = pc.store.keys()[0]
    path = pc.store._entry_path(key)
    with open(path, "rb") as f:
        blob = f.read()
    with open(path, "wb") as f:
        f.write(blob[: len(blob) // 2] + b"\xff" * 16 + blob[len(blob) // 2 :])

    log = EventLog(log_path, rank=0)
    healed = ProgramCache(store=ExecutableStore(str(tmp_path)), log=log)
    compiled = healed.compile(_fn, *_avals(), name="t")
    log.close()
    assert healed.rejected == 1 and healed.misses == 1
    # the heal re-stored a GOOD entry: a third cache deserializes again
    third = ProgramCache(store=ExecutableStore(str(tmp_path)))
    third.compile(_fn, *_avals(), name="t")
    assert third.deserialized == 1
    names = [e["name"] for e in read_events(log_path)]
    assert "compile_cache_reject" in names and "compile_cache_miss" in names
    assert compiled is not None


def test_wrap_jit_dispatch_and_cache_size(tmp_path):
    pc = ProgramCache(store=ExecutableStore(str(tmp_path)))
    w = pc.wrap_jit(jax.jit(_fn), name="w")
    x, wgt = np.ones((8, 16), np.float32), np.ones((16, 16), np.float32)
    a = float(w(x, wgt))
    assert w._cache_size() == 1 and pc.misses == 1
    b = float(w(x, wgt))  # table hit: no new program
    assert w._cache_size() == 1 and pc.misses == 1 and a == b
    w(np.ones((4, 16), np.float32), wgt)  # new shape -> second program
    assert w._cache_size() == 2 and pc.misses == 2


def _tree(dtype=jnp.float32, shape=(16, 16)):
    return {"w": jnp.ones(shape, dtype), "more": [jnp.zeros((3,), jnp.float32), jnp.ones((2, 2), jnp.bfloat16)]}


def _tree_fn(p, x):
    return (x @ p["w"].astype(jnp.float32)[: x.shape[1]]).sum() + p["more"][0].sum()


def test_wrap_jit_keeps_the_signature_of_a_tree_that_comes_back():
    """The same tree twice: one executable, and the second call signs the other argument alone (the tree's
    three leaves are looked at by identity, not signed); ``stats()`` carries both counts."""
    pc = ProgramCache()
    w = pc.wrap_jit(jax.jit(_tree_fn), name="w")
    tree = _tree()
    a = float(w(tree, jnp.ones((8, 16))))
    assert (pc.leaves_signed, pc.signatures_kept) == (4, 0) and w._cache_size() == 1
    b = float(w(tree, jnp.ones((8, 16))))  # a new array of the old signature beside the old tree
    assert (pc.leaves_signed, pc.signatures_kept) == (5, 1) and w._cache_size() == 1 and pc.misses == 1 and a == b
    x = jnp.ones((8, 16))
    w(tree, x), w(tree, x)
    assert (pc.leaves_signed, pc.signatures_kept) == (6, 4), "both arguments came back the second time"
    assert pc.stats()["leaves_signed"] == 6 and pc.stats()["signatures_kept"] == 4
    w(_tree(), x)  # an equal tree of other arrays: signed anew, the same executable
    assert (pc.leaves_signed, pc.signatures_kept) == (9, 5) and w._cache_size() == 1 and pc.misses == 1


def _on_another_device(tree):
    return {**tree, "w": jax.device_put(tree["w"], jax.devices()[1])}


@pytest.mark.parametrize(
    "other",
    [lambda t: {**t, "w": t["w"].astype(jnp.bfloat16)}, lambda t: {**t, "w": jnp.ones((32, 16), jnp.float32)}, _on_another_device],
    ids=["dtype", "shape", "sharding"],
)
def test_wrap_jit_new_tree_with_one_leaf_changed_is_a_second_executable(other):
    """One leaf of another dtype, shape or sharding among leaves that are the very arrays of the call before."""
    pc = ProgramCache()
    w = pc.wrap_jit(jax.jit(lambda p: jax.tree.map(lambda l: l * 2, p)), name="w")
    tree = _tree()
    w(tree), w(tree)
    assert w._cache_size() == 1 and pc.signatures_kept == 1
    changed = other(tree)
    out = w(changed)
    assert w._cache_size() == 2 and pc.misses == 2
    assert out["w"].dtype == changed["w"].dtype and out["w"].shape == changed["w"].shape
    assert out["w"].sharding == changed["w"].sharding
    w(tree)  # and back: the first executable, no third
    assert w._cache_size() == 2 and pc.misses == 2


@pytest.mark.parametrize("how", ["leaf_replaced", "key_added", "list_grown"])
def test_wrap_jit_tree_mutated_in_place_is_not_served_the_stale_executable(how):
    """The dict that came back is the same object, its contents are not: the kept signature is checked against
    the leaves' identities and the treedef, so the call lowers for what the tree now is."""
    pc = ProgramCache()
    w = pc.wrap_jit(jax.jit(lambda p: jax.tree.map(lambda l: l * 2, p)), name="w")
    tree = _tree()
    w(tree), w(tree)
    kept, size = pc.signatures_kept, w._cache_size()
    if how == "leaf_replaced":
        tree["w"] = jnp.ones((16, 16), jnp.bfloat16)
    elif how == "key_added":
        tree["bias"] = jnp.zeros((4,), jnp.float32)
    else:
        tree["more"].append(jnp.zeros((5,), jnp.int32))
    out = w(tree)
    assert pc.signatures_kept == kept and w._cache_size() == size + 1
    assert jax.tree.structure(out) == jax.tree.structure(tree)
    assert [l.dtype for l in jax.tree.leaves(out)] == [l.dtype for l in jax.tree.leaves(tree)]
    w(tree)
    assert pc.signatures_kept == kept + 1 and w._cache_size() == size + 1


def test_wrap_jit_signs_numpy_and_python_leaves_every_call_and_holds_nothing_alive():
    """An argument with a leaf that is no jax array can change under the same object (a numpy array's shape, a
    Python scalar's value): signed every call. And the last call's arrays are held weakly: dropping the
    caller's reference frees them."""
    import gc
    import weakref

    pc = ProgramCache()
    w = pc.wrap_jit(jax.jit(lambda p, n: p["x"] * n), name="w")
    batch = {"x": np.ones((4,), np.float32)}
    w(batch, 2.0), w(batch, 2.0)
    assert pc.signatures_kept == 0 and pc.leaves_signed == 4 and w._cache_size() == 1
    batch["x"].resize((8,), refcheck=False)  # the same numpy object, another shape
    assert w(batch, 2.0).shape == (8,) and w._cache_size() == 2
    big = {"x": jnp.ones((1024,), jnp.float32)}
    w(big, 2.0)
    ref = weakref.ref(big["x"])
    del big
    gc.collect()
    assert ref() is None, "the dispatch kept the last call's array alive"


def test_wrap_jit_watchdog_probe_and_export_see_what_they_saw(tmp_path):
    """The recompile watchdog's probe (``_cache_size`` through ``StepTelemetry.wrap``) stays silent over calls
    that keep their signatures and fires on the one that does not; ``aot_export`` ships one entry a program."""
    from accelerate_tpu.telemetry import StepTelemetry

    pc = ProgramCache(store=ExecutableStore(str(tmp_path / "store")))
    dispatch = pc.wrap_jit(jax.jit(_tree_fn), name="probe")
    telem = StepTelemetry(warmup_steps=1)
    step = telem.wrap(dispatch)
    tree, x = _tree(), jnp.ones((8, 16))
    for _ in range(4):
        step(tree, x)
    assert telem.recompiles == 0 and dispatch._cache_size() == 1 and pc.signatures_kept == 6
    tree["w"] = jnp.ones((16, 16), jnp.bfloat16)
    step(tree, x)
    assert telem.recompiles == 1 and dispatch._cache_size() == 2
    assert pc.aot_export(str(tmp_path / "bundle.tar.gz")) == 2 == pc.misses


def test_aot_export_import_roundtrip(tmp_path):
    src = ProgramCache(store=ExecutableStore(str(tmp_path / "src")))
    src.compile(_fn, *_avals(), name="t")
    archive = str(tmp_path / "bundle.tar.gz")
    assert src.aot_export(archive) == 1

    dst = ProgramCache(store=ExecutableStore(str(tmp_path / "dst")))
    assert dst.aot_load(archive) == 1
    dst.compile(_fn, *_avals(), name="t")
    assert (dst.misses, dst.deserialized) == (0, 1)


def test_resolve_cache_dir_precedence(tmp_path, monkeypatch):
    monkeypatch.delenv("ACCELERATE_COMPILE_CACHE_DIR", raising=False)
    assert resolve_cache_dir() is None
    assert resolve_cache_dir(project_dir="/p") == os.path.join("/p", "compile_cache")
    monkeypatch.setenv("ACCELERATE_COMPILE_CACHE_DIR", str(tmp_path))
    assert resolve_cache_dir(project_dir="/p") == str(tmp_path)
    assert resolve_cache_dir("/explicit", project_dir="/p") == "/explicit"


# --------------------------------------------------------------------- #
# cross-process warm start (the acceptance-criteria matrix)
# --------------------------------------------------------------------- #

_CHILD_COMPILE = """
import os, sys
sys.path.insert(0, {repo!r})
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from accelerate_tpu.aot import ExecutableStore, ProgramCache

mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
def step(x, w):
    return jnp.tanh(x @ w).sum()
pc = ProgramCache(store=ExecutableStore({store!r}))
sharded = jax.ShapeDtypeStruct((8, 16), jnp.float32, sharding=NamedSharding(mesh, P("data")))
dense = jax.ShapeDtypeStruct((16, 16), jnp.float32)
compiled = pc.compile(step, sharded, dense, name="xproc_step")
out = float(compiled(np.ones((8, 16), np.float32), np.ones((16, 16), np.float32)))
print("CHILD", pc.misses, pc.deserialized, out)
"""


def test_cross_process_cache_hit_matrix(tmp_path, monkeypatch):
    """The acceptance matrix: a subprocess compiles the (sharded-input)
    step into the store; this 'restarted' process re-creates the same
    program and performs ZERO XLA compiles — proved by the ProgramCache
    counters, the `compile_cache_hit` telemetry event, and the recompile
    watchdog staying at 0 across post-warm-start steps."""
    store_dir = str(tmp_path / "store")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD_COMPILE.format(repo=REPO, store=store_dir)],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    child = out.stdout.strip().splitlines()[-1].split()
    assert child[:3] == ["CHILD", "1", "0"]  # child compiled, nothing to deserialize

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from accelerate_tpu.telemetry import StepTelemetry

    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))

    def step(x, w):
        return jnp.tanh(x @ w).sum()

    log_path = str(tmp_path / "run.jsonl")
    log = EventLog(log_path, rank=0)
    pc = ProgramCache(store=ExecutableStore(store_dir), log=log)
    sharded = jax.ShapeDtypeStruct((8, 16), jnp.float32, sharding=NamedSharding(mesh, P("data")))
    dense = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    compiled = pc.compile(step, sharded, dense, name="xproc_step")
    assert pc.misses == 0 and pc.deserialized == 1  # zero XLA compiles here

    telem = StepTelemetry(log, warmup_steps=1)
    wrapped = telem.wrap(compiled)
    x = jax.device_put(np.ones((8, 16), np.float32), NamedSharding(mesh, P("data")))
    w = np.ones((16, 16), np.float32)
    results = [float(wrapped(x, w)) for _ in range(5)]
    log.close()
    assert telem.recompiles == 0
    assert results == [pytest.approx(float(child[3]))] * 5  # bit-consistent with the child
    events = read_events(log_path)
    hits = [e for e in events if e.get("name") == "compile_cache_hit"]
    assert hits and hits[0]["source"] == "disk" and hits[0]["deserialize_ms"] >= 0
    assert not [e for e in events if e.get("name") == "compile_cache_miss"]


# --------------------------------------------------------------------- #
# ShapeBucketer
# --------------------------------------------------------------------- #


def test_bucketer_minimal_covering_bucket():
    b = ShapeBucketer((8, 32, 128))
    assert b.bucket(3) == 8
    assert b.bucket(8) == 8
    assert b.bucket(9) == 32
    assert b.bucket(100) == 128


def test_bucketer_never_shrinks_and_grows_by_pow2():
    b = ShapeBucketer((8,))
    assert b.bucket(20) == 32  # minted: next_pow2(20)
    assert b.buckets == (8, 32)
    for n in (1, 7, 20, 31, 32):
        assert b.bucket(n) >= n
    before = set(b.buckets)
    b.refine()
    assert before.issubset(set(b.buckets))  # grow-only


def test_bucketer_multiple_of_and_max_size():
    b = ShapeBucketer((6,), multiple_of=4)
    assert b.buckets == (8,)  # seed rounded up to the shard multiple
    assert b.bucket(9) % 4 == 0
    capped = ShapeBucketer((8,), max_size=24, multiple_of=4)
    assert capped.bucket(17) == 24  # pow2 would be 32; clamped to max_size
    with pytest.raises(ValueError):
        capped.bucket(25)


def test_bucketer_refines_from_histogram():
    b = ShapeBucketer((64,), refine_every=10_000)  # refine manually
    for _ in range(50):
        b.bucket(17)
    added = b.refine()
    assert 17 in added and 17 in b.buckets
    assert b.bucket(17) == 17  # tighter bucket now wins
    assert b.bucket(18) == 64  # everything else unchanged


def test_next_pow2_and_pad_batch_tree():
    assert [next_pow2(n) for n in (1, 2, 3, 8, 9)] == [1, 2, 4, 8, 16]
    batch = {"x": np.arange(12).reshape(3, 4), "y": np.arange(3), "scalar": 7}
    padded = pad_batch_tree(batch, 8)
    assert padded["x"].shape == (8, 4) and padded["y"].shape == (8,)
    np.testing.assert_array_equal(padded["y"], [0, 1, 2, 0, 1, 2, 0, 1])  # wrap-around
    assert padded["scalar"] == 7
    assert pad_batch_tree(batch, 2)["x"].shape == (3, 4)  # never truncates


# --------------------------------------------------------------------- #
# auto-bucketing end to end: ragged stream, bounded compiles, quiet watchdog
# --------------------------------------------------------------------- #


def test_ragged_stream_bounded_compiles_watchdog_silent():
    """Acceptance: a stream of ragged batch shapes through auto-bucketing
    triggers at most len(buckets) compiles and the recompile watchdog is
    SILENT after warmup."""
    from accelerate_tpu.telemetry import StepTelemetry

    bucketer = ShapeBucketer((8, 16))
    pc = ProgramCache()
    dispatch = pc.wrap_jit(jax.jit(lambda b: (b["x"] * 2).sum()), name="ragged")
    telem = StepTelemetry(warmup_steps=2)
    step = telem.wrap(dispatch)

    rng = np.random.default_rng(0)
    sizes = [5, 13] + [int(rng.integers(1, 17)) for _ in range(50)]
    for n in sizes:  # first two cover both buckets during warmup
        batch = {"x": np.ones((n, 4), np.float32)}
        step(pad_batch_tree(batch, bucketer.bucket(n)))
    assert bucketer.buckets == (8, 16)
    assert pc.misses <= len(bucketer.buckets)
    assert dispatch._cache_size() <= len(bucketer.buckets)
    assert telem.recompiles == 0  # silent after warmup


def test_dataloader_auto_bucketing_pads_ragged_tail():
    from accelerate_tpu.data_loader import DataLoaderShard

    ds = [{"x": np.full((4,), i, np.float32)} for i in range(21)]
    dl = DataLoaderShard(
        ds, batch_size=8, even_batches=False, auto_bucketing=True, device_placement=False
    )
    shapes = [b["x"].shape for b in dl]
    # steady batches stay 8 (seeded bucket); the 5-row tail pads to 8 too
    assert shapes == [(8, 4), (8, 4), (8, 4)]
    assert dl.remainder == 5  # gather_for_metrics truncation still exact
    assert dl.bucketer.buckets == (8,)
    # wrap-around rows replay the batch head, even_batches tail semantics
    last = list(dl)[-1]
    np.testing.assert_array_equal(last["x"][:, 0], [16, 17, 18, 19, 20, 16, 17, 18])


def test_iterable_loader_auto_bucketing_single_program_shape():
    from accelerate_tpu.data_loader import IterableDataLoaderShard

    class Stream:
        def __iter__(self):
            for i in range(30):
                yield {"x": np.full((2,), i, np.float32)}

    dl = IterableDataLoaderShard(
        Stream(), batch_size=7, even_batches=False, auto_bucketing=True, device_placement=False
    )
    shapes = {b["x"].shape for b in dl}
    assert shapes == {(7, 2)}  # 4 full batches + 2-row tail, all one bucket
    assert dl.remainder == 2


# --------------------------------------------------------------------- #
# CompileKwargs / Accelerator wiring
# --------------------------------------------------------------------- #


def _make_accelerator(cache_dir):
    import optax

    from accelerate_tpu import Accelerator, CompileKwargs

    acc = Accelerator(kwargs_handlers=[CompileKwargs(cache_dir=cache_dir)])
    params = {"w": np.ones((4, 4), np.float32)}
    apply_fn = lambda p, x: x @ p["w"]  # noqa: E731
    model = acc.prepare_model((apply_fn, params))
    acc.prepare_optimizer(optax.sgd(0.1))
    step = acc.build_train_step(lambda p, b: ((apply_fn(p, b["x"]) - b["y"]) ** 2).mean())
    batch = {"x": np.ones((8, 4), np.float32), "y": np.zeros((8, 4), np.float32)}
    return acc, step, batch


def test_compile_kwargs_activates_program_cache(tmp_path, reset_singletons):
    from accelerate_tpu import Accelerator

    acc, step, batch = _make_accelerator(str(tmp_path))
    losses = [float(step(batch)) for _ in range(3)]
    assert acc.program_cache is not None and acc.program_cache.misses >= 1
    assert step._jitted._cache_size() >= 1  # watchdog probe works through the wrapper
    assert acc.program_cache.store is not None and len(acc.program_cache.store.keys()) >= 1

    # "restart": a fresh Accelerator + fresh ProgramCache over the same dir
    # rebuilds the same step with ZERO compiles and a bit-exact trajectory
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state(), GradientState._reset_state(), PartialState._reset_state()
    acc2, step2, batch2 = _make_accelerator(str(tmp_path))
    losses2 = [float(step2(batch2)) for _ in range(3)]
    assert losses2 == losses
    assert acc2.program_cache.misses == 0 and acc2.program_cache.deserialized >= 1


def test_train_step_through_the_program_cache_gives_the_plain_steps_losses(tmp_path, monkeypatch, reset_singletons):
    """``build_train_step`` dispatched by ``wrap_jit`` (a compile cache directory is set) and by plain ``jax.jit``
    (none is): the same losses step for step, and the cache signed every step's leaves (parameters and
    optimizer state are new arrays each step: nothing to keep)."""
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    acc, step, batch = _make_accelerator(str(tmp_path))
    cached = [float(step(batch)) for _ in range(4)]
    assert acc.program_cache.leaves_signed > 0 and step._jitted._cache_size() >= 1
    AcceleratorState._reset_state(), GradientState._reset_state(), PartialState._reset_state()
    monkeypatch.delenv("ACCELERATE_COMPILE_CACHE_DIR", raising=False)
    plain_acc = Accelerator()
    assert plain_acc.program_cache is None
    params = {"w": np.ones((4, 4), np.float32)}
    apply_fn = lambda p, x: x @ p["w"]  # noqa: E731
    plain_acc.prepare_model((apply_fn, params))
    plain_acc.prepare_optimizer(optax.sgd(0.1))
    plain = plain_acc.build_train_step(lambda p, b: ((apply_fn(p, b["x"]) - b["y"]) ** 2).mean())
    assert [float(plain(batch)) for _ in range(4)] == cached and cached[-1] < cached[0]


def test_bare_accelerator_has_no_program_cache(monkeypatch, reset_singletons):
    from accelerate_tpu import Accelerator

    monkeypatch.delenv("ACCELERATE_COMPILE_CACHE_DIR", raising=False)
    assert Accelerator().program_cache is None


def test_env_var_activates_program_cache(tmp_path, monkeypatch, reset_singletons):
    from accelerate_tpu import Accelerator

    monkeypatch.setenv("ACCELERATE_COMPILE_CACHE_DIR", str(tmp_path))
    acc = Accelerator()
    assert acc.program_cache is not None
    assert acc.program_cache.store.path == os.path.join(str(tmp_path), "executables")


# --------------------------------------------------------------------- #
# serving: lazy buckets + per-bucket compile_ms
# --------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def tiny_llama():
    from accelerate_tpu.models import LlamaConfig, create_llama_model

    return create_llama_model(LlamaConfig.tiny(), seq_len=16)


def test_serving_buckets_compile_lazily(tiny_llama, tmp_path):
    from accelerate_tpu.serving import ServingEngine

    log_path = str(tmp_path / "serve.jsonl")
    log = EventLog(log_path, rank=0)
    eng = ServingEngine(tiny_llama, num_slots=2, prompt_buckets=(4, 8, 16), telemetry_log=log)
    assert len(eng._prefill) == 0  # construction compiled NO prefill bucket
    eng.generate_many([np.arange(1, 6, dtype=np.int32)], max_new_tokens=3)
    assert eng._prefill.compiled_buckets() == (8,)  # only the bucket traffic hit
    assert ("prefill", 8) in eng.bucket_compile_ms and eng.bucket_compile_ms[("prefill", 8)] > 0
    log.close()
    events = [e for e in read_events(log_path) if e.get("name") == "serving_bucket_compile"]
    assert [(e["program"], e["bucket"]) for e in events] == [("prefill", 8)]
    assert events[0]["compile_ms"] > 0


_CHILD_SERVE = """
import os, sys
sys.path.insert(0, {repo!r})
from accelerate_tpu.utils.environment import force_host_platform
force_host_platform(1)
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
import numpy as np
from accelerate_tpu.models import LlamaConfig, create_llama_model
from accelerate_tpu.serving import ServingEngine
from accelerate_tpu.aot import ExecutableStore, ProgramCache

model = create_llama_model(LlamaConfig.tiny(), seq_len=16)
eng = ServingEngine(model, num_slots=1, prompt_buckets=(8,),
                    program_cache=ProgramCache(store=ExecutableStore({store!r})))
[ref] = eng.generate_many([np.arange(1, 7, dtype=np.int32)], max_new_tokens=3)
pc = eng.program_cache
print("REPLICA", pc.misses, pc.deserialized, " ".join(str(t) for t in ref))
"""


def test_serving_warm_replica_reuses_store(tmp_path):
    """The new-replica warm-start story: a cold replica fills the store,
    a second replica deserializes EVERY engine program with zero XLA
    compiles and token-exact output. Both replicas are real subprocesses
    — a replica is a fresh process by definition, and that is also the
    regime where XLA:CPU serialization is dependable (a long-lived
    process with many resident programs can emit non-self-contained
    blobs, which the ProgramCache reject-and-heal path downgrades to a
    recompile rather than a wrong result)."""
    store_dir = str(tmp_path / "serve_store")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("XLA_FLAGS", None)

    def replica():
        out = subprocess.run(
            [sys.executable, "-c", _CHILD_SERVE.format(repo=REPO, store=store_dir)],
            capture_output=True, text=True, env=env, timeout=300,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        tag, misses, deser, *tokens = out.stdout.strip().splitlines()[-1].split()
        assert tag == "REPLICA"
        return int(misses), int(deser), np.asarray([int(t) for t in tokens], np.int32)

    cold_misses, cold_deser, ref = replica()
    assert cold_misses >= 1 and cold_deser == 0

    warm_misses, warm_deser, got = replica()
    assert warm_misses == 0, "warm replica must not compile"
    assert warm_deser == cold_misses  # every program came from the store
    np.testing.assert_array_equal(got, ref)


# --------------------------------------------------------------------- #
# watchdog suggested_bucket + CLI
# --------------------------------------------------------------------- #


def test_watchdog_suggests_pad_bucket():
    from accelerate_tpu.telemetry import StepTelemetry

    st = StepTelemetry(warmup_steps=1)
    step = st.wrap(jax.jit(lambda x: x.sum()))
    step(jnp.ones((7, 128)))
    step(jnp.ones((7, 128)))
    step(jnp.ones((5, 128)))  # post-warmup drift on dim 0
    assert st.recompiles == 1
    (ev,) = st.recompile_events
    assert any("pad to float32[8,128]" in s for s in ev["suggested_bucket"])


def test_watchdog_no_suggestion_for_dtype_change():
    from accelerate_tpu.telemetry import StepTelemetry

    st = StepTelemetry(warmup_steps=1)
    step = st.wrap(jax.jit(lambda x: x.sum()))
    step(jnp.ones((8, 8)))
    step(jnp.ones((8, 8)))
    step(jnp.ones((8, 8), jnp.bfloat16))  # dtype drift: padding can't fix
    assert st.recompiles == 1
    assert st.recompile_events[0]["suggested_bucket"] == []


def _run_cli(*argv, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "accelerate_tpu.commands.cli", *argv],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300,
    )


@pytest.mark.slow
def test_cli_compile_cache_selfcheck():
    out = _run_cli("compile-cache", "--selfcheck")
    assert out.returncode == 0, out.stdout + out.stderr
    assert "poisoned entry rejected" in out.stdout


@pytest.mark.slow
def test_cli_compile_cache_warm_stats_clear(tmp_path):
    fn_file = tmp_path / "stepfn.py"
    fn_file.write_text(
        "import jax.numpy as jnp\n\ndef step(x, w):\n    return jnp.tanh(x @ w).sum()\n"
    )
    d = str(tmp_path / "cache")
    out = _run_cli(
        "compile-cache", "warm", f"{fn_file}::step", "--arg", "f32[8,16]", "--arg", "f32[16,16]",
        "--dir", d,
    )
    assert out.returncode == 0 and "compiled + stored" in out.stdout, out.stdout + out.stderr
    out = _run_cli(
        "compile-cache", "warm", f"{fn_file}::step", "--arg", "f32[8,16]", "--arg", "f32[16,16]",
        "--dir", d,
    )
    assert "deserialized (already warm)" in out.stdout

    out = _run_cli("compile-cache", "stats", "--dir", d, "--format", "json")
    report = json.loads(out.stdout)
    assert report["entries"] == 1 and report["programs"][0]["name"] == "step"

    out = _run_cli("compile-cache", "clear", "--dir", d)
    assert "would remove 1" in out.stdout  # dry-run by default
    out = _run_cli("compile-cache", "clear", "--dir", d, "--yes")
    assert "removed 1" in out.stdout
    out = _run_cli("compile-cache", "stats", "--dir", d, "--format", "json")
    assert json.loads(out.stdout)["entries"] == 0
