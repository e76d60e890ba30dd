"""Converter CLI: trees/io JSON -> ITRF binary artifact.

Closes the paper's dataset -> deployable-artifact loop at the command line:

    python -m repro.trees.convert model.json model.itrf
    python -m repro.trees.convert model.json model.itrf --strip-float --pack-leaves
    python -m repro.trees.convert --inspect model.itrf
    python -m repro.trees.convert --selftest /tmp/demo.itrf

``--strip-float`` drops the float threshold/leaf-probability sections
(deterministic-serving artifact, roughly half the bytes); ``--pack-leaves``
stores the fixed-point leaf table through the exact group codec
(:mod:`repro.ir.packed_leaf`).  ``--inspect`` dumps the header and the
section table without loading array pages.

``--selftest`` is the end-to-end proof CI runs: train a small forest, write
its JSON, convert, then digest the source forest and the mmap-reloaded
artifact, each **in a fresh process**, and assert the two reference partials
are bit-identical (``--verify`` is that subprocess entry point: it prints
``PARTIALS_SHA256 <hex>`` for deterministic probe rows of a ``.json`` model
or an ``.itrf`` artifact).  The parent never touches JAX, so on an
accelerator host the children can each hold the device in turn.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import subprocess
import sys
import tempfile


def _partials_digest(ir, rows: int = 64, seed: int = 0) -> str:
    """SHA-256 of the reference backend's integer partials on deterministic
    probe rows — the cross-process identity fingerprint."""
    import numpy as np

    from repro.backends import create_backend

    rng = np.random.default_rng(seed)
    X = rng.standard_normal((rows, ir.n_features)).astype(np.float32)
    backend = create_backend("reference", ir.materialize("padded"),
                             mode="integer")
    acc = np.ascontiguousarray(np.asarray(backend.predict_partials(X)),
                               dtype="<u4")
    return hashlib.sha256(acc.tobytes()).hexdigest()


def _convert(args) -> int:
    from repro.ir import ForestIR
    from repro.trees.io import forest_from_json

    with open(args.input) as fh:
        forest = forest_from_json(fh.read())
    ir = ForestIR.from_forest(forest)
    info = ir.to_itrf(args.output, include_float=not args.strip_float,
                      pack_leaves=args.pack_leaves, group=args.group)
    sizes = ir.nbytes_by_layout("integer")
    print(f"wrote {info['path']}: {info['file_bytes']} bytes, "
          f"sections {info['sections']}")
    print("layout bytes (integer): "
          + "; ".join(f"{k}={v}" for k, v in sorted(sizes.items())))
    return 0


def _inspect(path) -> int:
    from repro.ir.artifact import inspect_itrf

    print(json.dumps(inspect_itrf(path), indent=2))
    return 0


def _verify(path) -> int:
    from repro.ir import ForestIR

    if str(path).endswith(".json"):
        from repro.trees.io import forest_from_json

        with open(path) as fh:
            ir = ForestIR.from_forest(forest_from_json(fh.read()))
    else:
        ir = ForestIR.from_itrf(path, mmap=True)
    print(f"PARTIALS_SHA256 {_partials_digest(ir)}")
    return 0


def _child_digest(path):
    """``--verify path`` in a fresh interpreter -> (returncode, digest)."""
    out = subprocess.run([sys.executable, "-m", "repro.trees.convert",
                          "--verify", str(path)],
                         capture_output=True, text=True, timeout=600)
    sys.stderr.write(out.stderr)
    got = None
    for line in out.stdout.splitlines():
        if line.startswith("PARTIALS_SHA256 "):
            got = line.split(None, 1)[1].strip()
    return out.returncode, got


def _selftest(out_path) -> int:
    from repro.data.tabular import make_shuttle_like, train_test_split
    from repro.trees.forest import RandomForestClassifier
    from repro.trees.io import forest_to_json

    Xtr, ytr, _, _ = train_test_split(*make_shuttle_like(n=1500, seed=0),
                                      seed=0)
    rf = RandomForestClassifier(n_estimators=10, max_depth=8, seed=0).fit(
        Xtr, ytr)
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as fh:
        fh.write(forest_to_json(rf))
        json_path = fh.name
    rc = main([json_path, out_path, "--pack-leaves"])
    if rc:
        return rc
    # this process stays off JAX, so each child may claim the device: the
    # first digests the source forest, the second mmaps the artifact and
    # must reproduce those partials bit-for-bit
    rc_src, expect = _child_digest(json_path)
    rc_art, got = _child_digest(out_path)
    if rc_src or rc_art or expect is None or got != expect:
        print(f"SELFTEST FAIL: artifact digest {got} != source {expect}")
        return 1
    print(f"SELFTEST OK: fresh-process mmap reload bit-identical ({expect})")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.trees.convert",
        description="Convert trees/io JSON models to ITRF binary artifacts.")
    ap.add_argument("input", nargs="?", help="model JSON path")
    ap.add_argument("output", nargs="?", help="output .itrf path")
    ap.add_argument("--strip-float", action="store_true",
                    help="omit float threshold/leaf-probability sections")
    ap.add_argument("--pack-leaves", action="store_true",
                    help="group-quantize/bit-pack the fixed-point leaf table")
    ap.add_argument("--group", type=int, default=None,
                    help="codec group size (default 64)")
    ap.add_argument("--inspect", metavar="ITRF",
                    help="dump an artifact's header/section table as JSON")
    ap.add_argument("--verify", metavar="ITRF|JSON",
                    help="load an artifact (mmap) or a model JSON and print "
                         "its partials digest")
    ap.add_argument("--selftest", metavar="OUT_ITRF",
                    help="train, convert, and verify in a fresh process")
    args = ap.parse_args(argv)
    if args.inspect:
        return _inspect(args.inspect)
    if args.verify:
        return _verify(args.verify)
    if args.selftest:
        return _selftest(args.selftest)
    if not args.input or not args.output:
        ap.error("need INPUT.json and OUTPUT.itrf (or one of --inspect/"
                 "--verify/--selftest)")
    return _convert(args)


if __name__ == "__main__":
    raise SystemExit(main())
