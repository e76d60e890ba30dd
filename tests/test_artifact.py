"""ITRF binary artifact suite: the deployment boundary's guarantees.

Round-trip bit-identity (every IR array, dtype and value, including the
degenerate forests and a multi-word-bitvector chain), loud refusal of
newer-major artifacts (mirroring the trees/io schema gating), mmap read-only
safety, the packed-leaf group/dictionary codec's exactness at its edges,
registry retention + hot-swap page reuse, older files carrying the retired
tune-DB section, the worker HELLO artifact-bytes fast path, and the
converter CLI.
"""
import gc
import json
import os
import weakref

import numpy as np
import pytest

from repro.ir import ForestIR
from repro.ir.artifact import (
    FLAG_FLOAT,
    FLAG_PACKED_LEAVES,
    ITRF_VERSION,
    inspect_itrf,
    read_itrf,
    read_itrf_bytes,
    write_itrf,
)
from repro.ir.packed_leaf import (
    pack_groups,
    pack_leaf_payload,
    unpack_groups,
    unpack_leaf_payload,
)

from forest_cases import DEGENERATE_FORESTS, chain_tree, forest_from_trees

IR_ARRAYS = ("feature", "threshold", "threshold_key", "left", "right",
             "leaf_probs", "leaf_fixed", "node_offsets", "tree_depths")


def _assert_ir_equal(a: ForestIR, b: ForestIR, *, msg=""):
    for name in IR_ARRAYS:
        x, y = getattr(a, name), getattr(b, name)
        assert x.dtype == y.dtype, f"{msg}{name} dtype {x.dtype} != {y.dtype}"
        np.testing.assert_array_equal(x, y, err_msg=f"{msg}{name}")
    assert (a.n_trees, a.n_classes, a.n_features, a.quant_scale) == \
           (b.n_trees, b.n_classes, b.n_features, b.quant_scale)


@pytest.fixture(scope="module")
def trained_ir(small_forest):
    return ForestIR.from_forest(small_forest)


# ------------------------------------------------------------- round trips

@pytest.mark.parametrize("mmap_arrays", [True, False], ids=["mmap", "eager"])
@pytest.mark.parametrize("kwargs", [
    {},
    {"include_float": False},
    {"pack_leaves": True},
    {"include_float": False, "pack_leaves": True},
], ids=["full", "stripped", "packed", "stripped+packed"])
def test_round_trip_trained(trained_ir, tmp_path, kwargs, mmap_arrays):
    path = tmp_path / "m.itrf"
    info = trained_ir.to_itrf(str(path), **kwargs)
    assert info["file_bytes"] == os.path.getsize(path)
    out = ForestIR.from_itrf(str(path), mmap=mmap_arrays)
    if kwargs.get("include_float", True):
        _assert_ir_equal(trained_ir, out)
    else:
        # deterministic-only artifact: float tables load as zeros, every
        # integer-side array still round-trips exactly
        for name in IR_ARRAYS:
            if name in ("threshold", "leaf_probs"):
                assert not np.asarray(getattr(out, name)).any()
            else:
                np.testing.assert_array_equal(getattr(trained_ir, name),
                                              getattr(out, name),
                                              err_msg=name)
    assert out.itrf_version == ITRF_VERSION
    assert bool(out.itrf_flags & FLAG_PACKED_LEAVES) == \
           bool(kwargs.get("pack_leaves"))


@pytest.mark.parametrize("case", sorted(DEGENERATE_FORESTS))
@pytest.mark.parametrize("pack_leaves", [False, True], ids=["raw", "packed"])
def test_round_trip_degenerate(case, pack_leaves, tmp_path):
    """Stumps (T trees of one node), T == 1, and depth-skewed forests
    survive the binary boundary bit-for-bit."""
    ir = ForestIR.from_forest(DEGENERATE_FORESTS[case]())
    path = tmp_path / f"{case}.itrf"
    ir.to_itrf(str(path), pack_leaves=pack_leaves)
    _assert_ir_equal(ir, ForestIR.from_itrf(str(path)), msg=f"{case}: ")


def test_round_trip_multiword_bitvector_chain(tmp_path):
    """A depth-70 chain yields > 64 leaves per tree, so the bitvector layout
    needs multiple mask words; the artifact round trip must preserve the
    bit-identical serve through that layout too."""
    from repro.serve.engine import TreeEngine

    ir = ForestIR.from_forest(
        forest_from_trees([chain_tree(70, 3)], 3, 4))
    path = tmp_path / "chain.itrf"
    ir.to_itrf(str(path), pack_leaves=True)
    out = ForestIR.from_itrf(str(path))
    _assert_ir_equal(ir, out)
    assert out.materialize("bitvector").words > 1
    rows = np.random.default_rng(5).normal(0, 40, (33, 4)).astype(np.float32)
    s_ref, _ = TreeEngine(ir, "integer").predict_scores(rows)
    s_bv, _ = TreeEngine(out, "integer:bitvector").predict_scores(rows)
    np.testing.assert_array_equal(np.asarray(s_bv), np.asarray(s_ref))


def test_round_trip_single_stump(tmp_path):
    """The smallest possible artifact: one tree, one node."""
    ir = ForestIR.from_forest(forest_from_trees(
        [DEGENERATE_FORESTS["stumps"]().trees_[0]], 3, 4))
    path = tmp_path / "stump.itrf"
    ir.to_itrf(str(path), pack_leaves=True)
    _assert_ir_equal(ir, ForestIR.from_itrf(str(path)))


def test_inspect_reports_header_and_sections(trained_ir, tmp_path):
    path = tmp_path / "m.itrf"
    trained_ir.to_itrf(str(path), pack_leaves=True)
    info = inspect_itrf(str(path))
    assert info["version"] == list(ITRF_VERSION) or \
           info["version"] == ITRF_VERSION
    assert info["n_trees"] == trained_ir.n_trees
    assert info["total_nodes"] == trained_ir.total_nodes
    assert set(info["sections"]) >= {"feature", "threshold_key", "left",
                                     "right", "node_offsets", "tree_depths",
                                     "leaf_pack_data", "meta"}
    for ent in info["sections"].values():
        assert ent["offset"] % 64 == 0  # every section is 64-byte aligned


# --------------------------------------------------------- format gating

def _patch_header(path, **over):
    """Rewrite header fields in-place (test-only corruption helper)."""
    from repro.ir.artifact import _HEADER

    raw = bytearray(path.read_bytes())
    fields = list(_HEADER.unpack_from(raw))
    names = ["magic", "vmaj", "vmin", "flags", "n_trees", "n_classes",
             "n_features", "total_nodes", "quant_scale", "n_sections"]
    for k, v in over.items():
        fields[names.index(k)] = v
    raw[:_HEADER.size] = _HEADER.pack(*fields)
    path.write_bytes(bytes(raw))


def test_refuses_newer_major_version(trained_ir, tmp_path):
    """Mirror of trees/io schema gating: a future-major artifact is refused
    loudly, never half-parsed.  A newer *minor* still loads."""
    path = tmp_path / "m.itrf"
    trained_ir.to_itrf(str(path))
    _patch_header(path, vmaj=ITRF_VERSION[0] + 1)
    with pytest.raises(ValueError, match="format version"):
        read_itrf(str(path))
    with pytest.raises(ValueError, match="format version"):
        inspect_itrf(str(path))
    _patch_header(path, vmaj=ITRF_VERSION[0], vmin=ITRF_VERSION[1] + 7)
    out = read_itrf(str(path))
    _assert_ir_equal(trained_ir, out)
    assert out.itrf_version == (ITRF_VERSION[0], ITRF_VERSION[1] + 7)


def test_refuses_bad_magic_and_truncation(trained_ir, tmp_path):
    path = tmp_path / "m.itrf"
    trained_ir.to_itrf(str(path))
    _patch_header(path, magic=b"NOPE")
    with pytest.raises(ValueError, match="magic"):
        read_itrf(str(path))
    with pytest.raises(ValueError, match="not an ITRF"):
        read_itrf_bytes(b"IT")


def test_unknown_sections_are_skipped(trained_ir, tmp_path):
    """Minor versions may append sections; this reader must ignore names it
    does not know instead of failing."""
    from repro.ir.artifact import _parse_header, _parse_sections, \
        _section_array, _write_raw

    path = tmp_path / "m.itrf"
    trained_ir.to_itrf(str(path))
    ir = read_itrf(str(path), mmap_arrays=False)
    buf = path.read_bytes()
    head = _parse_header(buf)
    table = _parse_sections(buf, head["n_sections"])
    sections = [(n, _section_array(buf, e, copy=False))
                for n, e in table.items()]
    sections.append(("future_thing", np.arange(9, dtype=np.uint8)))
    _write_raw(str(path), (*head["version"], head["flags"], head["n_trees"],
                           head["n_classes"], head["n_features"],
                           head["total_nodes"],
                           int(head["quant_scale"] or 0)), sections)
    _assert_ir_equal(ir, read_itrf(str(path)))


# ------------------------------------------------------- mmap safety

def test_mmap_views_are_read_only_and_file_unchanged(trained_ir, tmp_path):
    from repro.serve.engine import TreeEngine

    path = tmp_path / "m.itrf"
    trained_ir.to_itrf(str(path))
    before = path.read_bytes()
    ir = ForestIR.from_itrf(str(path), mmap=True)
    for name in IR_ARRAYS:
        a = getattr(ir, name)
        assert not a.flags.writeable, f"{name} must be a read-only view"
        with pytest.raises((ValueError, RuntimeError)):
            a[...] = 0
    # serving goes through layout materializers, which copy — predicts must
    # neither fail on the read-only canon nor write back through the map
    rows = np.random.default_rng(0).normal(
        0, 4, (17, ir.n_features)).astype(np.float32)
    for mode in ("flint", "integer"):
        TreeEngine(ir, mode).predict_scores(rows)
    TreeEngine(ir, "integer:reference@packed_leaf").predict_scores(rows)
    assert path.read_bytes() == before
    # eager load is the opposite contract: private writable copies
    eager = ForestIR.from_itrf(str(path), mmap=False)
    assert eager.feature.flags.writeable
    eager.feature[0] = -1  # must not raise


# ------------------------------------------------- packed-leaf codec edges

def test_pack_groups_round_trip_edges():
    for values in (
        np.zeros(0, np.uint32),  # empty
        np.zeros(64, np.uint32),  # constant group, width 0
        np.full(7, 2**32 - 1, np.uint32),  # max values, partial group
        np.arange(200, dtype=np.uint32),  # multiple groups + tail
        np.array([0, 2**32 - 1] * 65, np.uint32),  # full-width deltas
    ):
        base, bits, payload = pack_groups(values, 64)
        out = unpack_groups(base, bits, payload, len(values), 64)
        np.testing.assert_array_equal(out, values)
        assert out.dtype == np.uint32


def test_pack_leaf_payload_picks_dictionary_for_near_one_hot():
    """Trained leaves are near-one-hot fixed-point rows: few distinct
    values, so the dictionary stage must win over raw group packing."""
    rng = np.random.default_rng(0)
    scale = (2**32 - 1) // 16
    values = rng.choice(
        np.array([0, scale // 2, scale], np.uint32), 4096).astype(np.uint32)
    dictionary, base, bits, payload = pack_leaf_payload(values, 64)
    assert dictionary.size == 3  # dict mode engaged
    out = unpack_leaf_payload(dictionary, base, bits, payload,
                              len(values), 64)
    np.testing.assert_array_equal(out, values)


def test_pack_leaf_payload_falls_back_to_raw_for_high_entropy():
    rng = np.random.default_rng(1)
    values = rng.integers(0, 2**32, 4096, dtype=np.uint32)
    dictionary, base, bits, payload = pack_leaf_payload(values, 64)
    assert dictionary.size == 0  # raw mode: a 4096-entry dict cannot win
    out = unpack_leaf_payload(dictionary, base, bits, payload,
                              len(values), 64)
    np.testing.assert_array_equal(out, values)


def test_packed_leaf_layout_registered_and_smaller(trained_ir):
    sizes = trained_ir.nbytes_by_layout(mode="integer")
    assert "packed_leaf" in sizes
    assert sizes["packed_leaf"] < sizes["padded"]


def test_packed_leaf_rejects_float_mode(trained_ir):
    from repro.backends import create_backend

    art = trained_ir.materialize("packed_leaf")
    with pytest.raises(ValueError, match="deterministic"):
        create_backend("reference", art, mode="float")


# ----------------------------------------------------- registry integration

@pytest.fixture()
def artifact_path(trained_ir, tmp_path):
    path = tmp_path / "reg.itrf"
    trained_ir.to_itrf(str(path))
    return str(path)


def test_register_artifact_serves_identically_to_json(
        small_forest, artifact_path, shuttle_small):
    from repro.serve.registry import ModelRegistry
    from repro.trees.io import forest_to_json

    _, _, Xte, _ = shuttle_small
    rows = Xte[:64]
    reg = ModelRegistry()
    mv_j = reg.register_json("j", forest_to_json(small_forest))
    mv_a = reg.register_artifact("a", artifact_path)
    assert mv_a.source == "artifact"
    for mode in ("flint", "integer"):
        np.testing.assert_array_equal(
            np.asarray(mv_a.engine(mode).predict(rows)),
            np.asarray(mv_j.engine(mode).predict(rows)))


def test_register_artifact_load_ms_lands_in_engine_ledger(artifact_path):
    from repro.serve.registry import ModelRegistry

    mv = ModelRegistry().register_artifact("m", artifact_path)
    eng = mv.engine("integer")
    assert "load" in eng.drain_compile_timings()
    # charged once: a second engine on the same version pays nothing
    assert "load" not in mv.engine("flint").drain_compile_timings()


def test_hot_swap_reuses_mapped_artifact(artifact_path):
    from repro.serve.registry import ModelRegistry

    reg = ModelRegistry()
    mv1 = reg.register_artifact("m", artifact_path)
    mv2 = reg.register_artifact("m", artifact_path)
    assert mv2.version == mv1.version + 1
    assert mv2.packed is mv1.packed  # the mapped IR object, pages shared
    # rewriting the file (mtime/size change) invalidates the cache entry
    ir = read_itrf(artifact_path, mmap_arrays=False)
    os.utime(artifact_path, ns=(1, 1))
    mv3 = reg.register_artifact("m", artifact_path)
    assert mv3.packed is not mv1.packed
    del ir


def test_retention_releases_swapped_out_versions(artifact_path):
    """The regression the retention policy exists for: versions beyond the
    keep-window must close their engines and become garbage-collectable."""
    from repro.serve.registry import ModelRegistry

    reg = ModelRegistry(retain=2)
    mv1 = reg.register_artifact("m", artifact_path)
    eng1 = mv1.engine("integer")
    ref = weakref.ref(eng1)
    mv2 = reg.register_artifact("m", artifact_path)
    assert not mv1.released  # still inside the window (current + previous)
    mv3 = reg.register_artifact("m", artifact_path)
    assert mv1.released and eng1.closed
    assert not mv2.released
    with pytest.raises(RuntimeError, match="released"):
        mv1.engine("integer")
    del eng1, mv1
    gc.collect()
    assert ref() is None, "released engine still referenced"
    # explicit release of the retained previous version
    reg.release("m", mv2.version)
    assert mv2.released
    with pytest.raises(ValueError, match="current"):
        reg.release("m", mv3.version)
    with pytest.raises(KeyError):
        reg.release("m", mv2.version)  # already gone from the window
    assert reg.get("m") is mv3  # current version untouched throughout


def test_registry_retain_validation():
    from repro.serve.registry import ModelRegistry

    with pytest.raises(ValueError, match="retain"):
        ModelRegistry(retain=0)


def test_gateway_prunes_closed_engines(artifact_path, shuttle_small):
    import asyncio

    from repro.serve.gateway import Gateway
    from repro.serve.registry import ModelRegistry

    _, _, Xte, _ = shuttle_small
    rows = Xte[:8]
    reg = ModelRegistry(retain=1)
    gw = Gateway(reg, "integer", max_delay_ms=0.5)
    reg.register_artifact("m", artifact_path)
    asyncio.run(gw.submit("m", rows))
    assert len(gw._engines) == 1
    reg.register_artifact("m", artifact_path)  # retain=1: v1 released now
    s2, _ = asyncio.run(gw.submit("m", rows))
    assert all(not e.closed for e in gw._engines.values())
    assert len(gw._engines) == 1  # the closed v1 engine was pruned
    asyncio.run(gw.close())


# --------------------------------------------------- retired tune-db section

def _with_retired_tune_db(src, dst):
    """``src`` rewritten as older writers left it after recording host-clock
    tuning winners: every section verbatim plus a JSON ``tune_db`` section,
    and flag bit 4 set."""
    from repro.ir import artifact as art

    with open(src, "rb") as fh:
        buf = fh.read()
    head = art._parse_header(buf)
    table = art._parse_sections(buf, head["n_sections"])
    db = {"x86_64+avx2": {"pallas|leaf_major|integer":
                          {"block_b": 128, "block_t": 8}}}
    sections = [(name, art._section_array(buf, entry, copy=False))
                for name, entry in table.items()]
    sections.append(("tune_db", np.frombuffer(json.dumps(db).encode(),
                                              np.uint8)))
    vmaj, vmin = head["version"]
    art._write_raw(dst, (vmaj, vmin, head["flags"] | 4, head["n_trees"],
                         head["n_classes"], head["n_features"],
                         head["total_nodes"], int(head["quant_scale"] or 0)),
                   sections)


def test_retired_tune_db_section_loads_and_serves_bit_identically(
        artifact_path, tmp_path, shuttle_small):
    from repro.serve.registry import ModelRegistry

    _, _, Xte, _ = shuttle_small
    rows = Xte[:70]  # one batch on the scan, and the 6-row tail gathers
    old = str(tmp_path / "old.itrf")
    _with_retired_tune_db(artifact_path, old)
    info = inspect_itrf(old)
    assert info["flags"] & 4 and "tune_db" in info["sections"]
    reg = ModelRegistry()
    got, want = [], []
    for mid, path, out in (("old", old, got), ("new", artifact_path, want)):
        mv = reg.register_artifact(mid, path)
        eng = mv.engine("integer:pallas@leaf_major")
        # the recorded winners are not applied: the default tiling serves
        assert eng.backend._tiling["block_b"] == 256
        assert eng.backend._tiling["block_t"] is None
        for part in (rows[:64], rows[64:]):
            out.append(eng.predict_scores(part))
    for (s_got, p_got), (s_want, p_want) in zip(got, want):
        np.testing.assert_array_equal(np.asarray(s_got), np.asarray(s_want))
        np.testing.assert_array_equal(np.asarray(p_got), np.asarray(p_want))
    assert reg.get("old").packed.itrf_flags & 4


# ------------------------------------------------- worker HELLO fast path

def test_worker_session_decodes_itrf_hello(trained_ir, tmp_path,
                                           shuttle_small):
    """The artifact-bytes fast path: a HELLO whose payload is one raw ITRF
    image (not the per-array directory) rebuilds the forest and serves the
    bit-identical shard partials."""
    from repro.serve import wire
    from repro.serve.worker import _Session
    from repro.backends import create_backend

    path = tmp_path / "w.itrf"
    trained_ir.to_itrf(str(path), include_float=False)
    ir = ForestIR.from_itrf(str(path))
    meta = {"artifact_format": "itrf", "mode": "integer",
            "model_id": "m", "version": 1,
            "shards": [{"shard": 0, "start": 0, "stop": ir.n_trees,
                        "backend": "reference"}]}
    payload = wire.encode_hello(meta, {"itrf": ir.itrf_bytes})
    session = _Session(payload)
    _assert_ir_equal(ir, session.ir)
    _, _, Xte, _ = shuttle_small
    rows = Xte[:19]
    backend, built = session.backend(0)
    assert built
    ref = create_backend("reference", trained_ir.materialize("padded"),
                         mode="integer")
    np.testing.assert_array_equal(
        np.asarray(backend.predict_partials(rows)),
        np.asarray(ref.predict_partials(rows)))


def test_remote_plan_prefers_artifact_bytes_when_smaller(trained_ir,
                                                         tmp_path):
    """The HELLO fast path is size-guarded: a stripped artifact image beats
    the per-array payload and ships whole; a full-float image (2x, thanks to
    f64 leaf_probs) must fall back to the array directory."""
    from repro.serve import wire

    stripped = tmp_path / "s.itrf"
    full = tmp_path / "f.itrf"
    trained_ir.to_itrf(str(stripped), include_float=False)
    trained_ir.to_itrf(str(full), include_float=True)
    wire_arrays_nbytes = sum(
        getattr(trained_ir, n).nbytes
        for n in ("feature", "threshold", "threshold_key", "left", "right",
                  "leaf_fixed", "node_offsets", "tree_depths"))
    assert ForestIR.from_itrf(str(stripped)).itrf_bytes.nbytes \
        <= wire_arrays_nbytes
    assert ForestIR.from_itrf(str(full)).itrf_bytes.nbytes \
        > wire_arrays_nbytes


# ------------------------------------------------------------ converter CLI

def test_convert_cli_and_inspect(small_forest, tmp_path, capsys):
    from repro.trees.convert import main
    from repro.trees.io import forest_to_json

    src = tmp_path / "model.json"
    dst = tmp_path / "model.itrf"
    src.write_text(forest_to_json(small_forest))
    assert main([str(src), str(dst), "--strip-float", "--pack-leaves"]) == 0
    out = capsys.readouterr().out
    assert "packed_leaf=" in out and "bitvector=" in out
    ir = ForestIR.from_itrf(str(dst))
    assert ir.itrf_flags & FLAG_PACKED_LEAVES
    assert not ir.itrf_flags & FLAG_FLOAT
    ref = ForestIR.from_forest(small_forest)
    for name in ("feature", "threshold_key", "left", "right", "leaf_fixed",
                 "node_offsets", "tree_depths"):
        np.testing.assert_array_equal(getattr(ref, name), getattr(ir, name),
                                      err_msg=name)
    assert main(["--inspect", str(dst)]) == 0
    info = json.loads(capsys.readouterr().out)
    assert info["n_trees"] == small_forest.n_estimators


def test_convert_cli_requires_paths(capsys):
    from repro.trees.convert import main

    with pytest.raises(SystemExit):
        main([])
