"""ITRF: the versioned binary forest artifact (mmap-able ForestIR).

The trees/io JSON document is the *interchange* boundary; ITRF is the
*deployment* boundary — the struct-packed binary a production fleet loads.
The file is a fixed little-endian header, a section table, and 64-byte
aligned sections holding the IR's CSR arrays verbatim:

    header  := magic(4s=b"ITRF") version_major(u16) version_minor(u16)
               flags(u32) n_trees(u32) n_classes(u32) n_features(u32)
               total_nodes(u64) quant_scale(u64, 0 = derive from n_trees)
               n_sections(u32), zero-padded to 64 bytes
    section := name(16s, NUL-padded) dtype(8s, numpy str e.g. b"<i4")
               ndim(u32) shape(4 x u64) offset(u64, 64-aligned) nbytes(u64)

Loading with ``mmap=True`` maps the file read-only and returns a
:class:`~repro.ir.forest_ir.ForestIR` whose arrays are numpy views over the
mapping: zero copies, O(1) in forest size, and N co-resident processes
share one page cache.  The views are immutable (numpy refuses writes), and
every layout materializer already copies into fresh arrays, so backends
that need writable or device-resident data pay lazily per layout while the
canonical arrays stay shared.

Versioning mirrors ``trees/io``: a newer *major* version is refused loudly
(never half-parsed), unknown section names are skipped (minor versions may
add sections), and required sections missing raise.  One optional section
family rides along: ``leaf_pack_*``, the group-quantized leaf payload
(``--pack-leaves``), the exact codec from :mod:`repro.ir.packed_leaf`,
decoded on load (the one deliberate copy of that path).
"""
from __future__ import annotations

import json
import mmap
import os
import struct
import tempfile

import numpy as np

__all__ = [
    "ITRF_MAGIC", "ITRF_VERSION",
    "write_itrf", "read_itrf", "read_itrf_bytes", "inspect_itrf",
]

ITRF_MAGIC = b"ITRF"
ITRF_VERSION = (1, 0)  # (major, minor): major bumps break readers

FLAG_FLOAT = 1  # threshold/leaf_probs sections present
FLAG_PACKED_LEAVES = 2  # leaf_pack_* sections replace leaf_fixed
# flag 4 is retired: it marked a "tune_db" section of host-clock tuning
# winners.  Files that carry it still load (the reader ignores the bit and
# skips the section like any unknown one); never reuse the bit.

_ALIGN = 64
_HEADER = struct.Struct("<4sHHIIIIQQI")  # 44 bytes, padded to _ALIGN
_SECTION = struct.Struct("<16s8sI4QQQ")  # name dtype ndim shape[4] off nbytes

# sections a reader must find to rebuild the IR (leaf payload checked apart)
_NODE_SECTIONS = ("feature", "threshold_key", "left", "right",
                  "node_offsets", "tree_depths")


def _align(n: int) -> int:
    return -(-n // _ALIGN) * _ALIGN


# ---------------------------------------------------------------------------
# writer
# ---------------------------------------------------------------------------

def _le(a: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(a)
    return a.astype(a.dtype.newbyteorder("<"), copy=False)


def _write_raw(path, header_fields: tuple, sections: list) -> None:
    """Serialize (header, [(name, ndarray)]) to ``path`` atomically."""
    entries, blobs = [], []
    offset = _align(_HEADER.size) + _align(_SECTION.size * len(sections))
    for name, a in sections:
        a = _le(a)
        nm = name.encode()
        if len(nm) > 16:
            raise ValueError(f"section name {name!r} exceeds 16 bytes")
        if a.ndim > 4:
            raise ValueError(f"section {name!r} has ndim {a.ndim} > 4")
        shape = list(a.shape) + [0] * (4 - a.ndim)
        entries.append(_SECTION.pack(nm, a.dtype.str.encode(), a.ndim,
                                     *shape, offset, a.nbytes))
        blobs.append(a.tobytes())
        offset += _align(a.nbytes)
    head = _HEADER.pack(ITRF_MAGIC, *header_fields, len(sections))
    parts = [head, b"\0" * (_align(_HEADER.size) - len(head))]
    table = b"".join(entries)
    parts += [table, b"\0" * (_align(_SECTION.size * len(sections)) - len(table))]
    for blob in blobs:
        parts += [blob, b"\0" * (_align(len(blob)) - len(blob))]
    dirname = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=dirname, suffix=".itrf.tmp")
    try:
        with os.fdopen(fd, "wb") as fh:
            fh.write(b"".join(parts))
        os.replace(tmp, path)  # atomic: readers see old or new, never torn
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def write_itrf(path, ir, *, include_float: bool = True,
               pack_leaves: bool = False, group: int = None) -> dict:
    """Serialize ``ir`` (a ForestIR) as an ITRF file; returns a summary dict.

    ``include_float=False`` drops the float sections (threshold/leaf_probs)
    — a deterministic-serving artifact at roughly half the bytes; loading
    it yields zero float arrays, so only flint/integer routes may serve it.
    ``pack_leaves=True`` stores the leaf table through the exact group
    codec.
    """
    from repro.ir.packed_leaf import GROUP_SIZE, pack_leaf_payload

    group = int(group or GROUP_SIZE)
    flags = 0
    sections = [
        ("feature", ir.feature.astype(np.int32, copy=False)),
        ("threshold_key", ir.threshold_key.astype(np.int32, copy=False)),
        ("left", ir.left.astype(np.int32, copy=False)),
        ("right", ir.right.astype(np.int32, copy=False)),
        ("node_offsets", ir.node_offsets.astype(np.int64, copy=False)),
        ("tree_depths", ir.tree_depths.astype(np.int32, copy=False)),
    ]
    if pack_leaves:
        flags |= FLAG_PACKED_LEAVES
        values = ir.leaf_fixed[ir.feature < 0].ravel()
        dictionary, base, bits, payload = pack_leaf_payload(values, group)
        sections += [("leaf_pack_dict", dictionary),
                     ("leaf_pack_base", base), ("leaf_pack_bits", bits),
                     ("leaf_pack_data", payload)]
    else:
        sections.append(("leaf_fixed", ir.leaf_fixed.astype(np.uint32,
                                                            copy=False)))
    if include_float:
        flags |= FLAG_FLOAT
        sections += [
            ("threshold", ir.threshold.astype(np.float32, copy=False)),
            ("leaf_probs", ir.leaf_probs.astype(np.float64, copy=False)),
        ]
    meta = {"group_size": group}
    sections.append(("meta", np.frombuffer(json.dumps(meta).encode(),
                                           np.uint8)))
    header = (*ITRF_VERSION, flags, ir.n_trees, ir.n_classes, ir.n_features,
              ir.total_nodes, int(ir.quant_scale or 0))
    _write_raw(path, header, sections)
    return {"path": str(path), "flags": flags,
            "sections": [name for name, _ in sections],
            "file_bytes": os.path.getsize(path)}


# ---------------------------------------------------------------------------
# reader
# ---------------------------------------------------------------------------

def _parse_header(buf) -> dict:
    if len(buf) < _HEADER.size:
        raise ValueError(f"not an ITRF artifact: {len(buf)} bytes")
    (magic, vmaj, vmin, flags, n_trees, n_classes, n_features, total_nodes,
     quant_scale, n_sections) = _HEADER.unpack_from(buf)
    if magic != ITRF_MAGIC:
        raise ValueError(f"not an ITRF artifact: bad magic {magic!r}")
    if vmaj > ITRF_VERSION[0]:
        # mirror trees/io schema gating: refuse loudly, never half-parse
        raise ValueError(
            f"ITRF artifact uses format version {vmaj}.{vmin}, but this "
            f"reader understands <= {ITRF_VERSION[0]}.x; refusing to "
            f"half-parse a newer artifact"
        )
    return dict(version=(vmaj, vmin), flags=flags, n_trees=n_trees,
                n_classes=n_classes, n_features=n_features,
                total_nodes=total_nodes,
                quant_scale=quant_scale or None, n_sections=n_sections)


def _parse_sections(buf, n_sections: int) -> dict:
    """-> {name: (dtype_str, shape, offset, nbytes)} from the section table."""
    out = {}
    off = _align(_HEADER.size)
    for _ in range(n_sections):
        name, dt, ndim, s0, s1, s2, s3, offset, nbytes = \
            _SECTION.unpack_from(buf, off)
        shape = tuple(int(s) for s in (s0, s1, s2, s3)[:ndim])
        out[name.rstrip(b"\0").decode()] = (dt.rstrip(b"\0").decode(),
                                            shape, int(offset), int(nbytes))
        off += _SECTION.size
    return out


def _section_array(buf, entry, *, copy: bool) -> np.ndarray:
    dt_str, shape, offset, nbytes = entry
    dt = np.dtype(dt_str)
    count = int(np.prod(shape, dtype=np.int64)) if shape else 1
    a = np.frombuffer(buf, dt, count=count, offset=offset).reshape(shape)
    return a.copy() if copy else a


def _parse(buf, *, copy: bool, source=None):
    """Rebuild a ForestIR over ``buf`` (mmap, bytes, or memoryview)."""
    from repro.ir.forest_ir import ForestIR
    from repro.ir.packed_leaf import GROUP_SIZE, unpack_leaf_payload

    head = _parse_header(buf)
    table = _parse_sections(buf, head["n_sections"])
    missing = [n for n in _NODE_SECTIONS if n not in table]
    if missing:
        raise ValueError(f"ITRF artifact missing required sections {missing}")
    sec = lambda name: _section_array(buf, table[name], copy=copy)
    meta = {}
    if "meta" in table:
        meta = json.loads(_section_array(buf, table["meta"],
                                         copy=False).tobytes())
    total, C = head["total_nodes"], head["n_classes"]
    feature = sec("feature")
    if head["flags"] & FLAG_PACKED_LEAVES:
        values = unpack_leaf_payload(
            sec("leaf_pack_dict") if "leaf_pack_dict" in table
            else np.zeros(0, np.uint32),
            sec("leaf_pack_base"),
            sec("leaf_pack_bits"), sec("leaf_pack_data"),
            int((feature < 0).sum()) * C,
            int(meta.get("group_size", GROUP_SIZE)),
        )
        leaf_fixed = np.zeros((total, C), np.uint32)
        leaf_fixed[feature < 0] = values.reshape(-1, C)
    elif "leaf_fixed" in table:
        leaf_fixed = sec("leaf_fixed")
    else:
        raise ValueError("ITRF artifact carries neither leaf_fixed nor "
                         "leaf_pack_* sections")
    if head["flags"] & FLAG_FLOAT:
        threshold, leaf_probs = sec("threshold"), sec("leaf_probs")
    else:  # deterministic-only artifact: float tables are zero
        threshold = np.zeros(total, np.float32)
        leaf_probs = np.zeros((total, C), np.float64)
    ir = ForestIR(
        feature=feature,
        threshold=threshold,
        threshold_key=sec("threshold_key"),
        left=sec("left"),
        right=sec("right"),
        leaf_probs=leaf_probs,
        leaf_fixed=leaf_fixed,
        node_offsets=sec("node_offsets"),
        tree_depths=sec("tree_depths"),
        n_trees=head["n_trees"],
        n_classes=C,
        n_features=head["n_features"],
        quant_scale=head["quant_scale"],
    )
    # artifact provenance, read by the registry (load ledger) and the
    # remote plan (HELLO ships the raw artifact bytes)
    ir.itrf_source = str(source) if source is not None else None
    ir.itrf_version = head["version"]
    ir.itrf_flags = head["flags"]
    ir.itrf_bytes = np.frombuffer(buf, np.uint8)
    return ir


def read_itrf(path, *, mmap_arrays: bool = True):
    """Load an ITRF file -> ForestIR.

    ``mmap_arrays=True`` (the default) maps the file read-only and returns
    zero-copy views: O(1) load regardless of forest size, pages shared with
    every other process mapping the same file.  ``mmap_arrays=False`` reads
    the file eagerly and returns private writable copies.
    """
    with open(path, "rb") as fh:
        if mmap_arrays:
            mm = mmap.mmap(fh.fileno(), 0, access=mmap.ACCESS_READ)
            return _parse(mm, copy=False, source=path)
        return _parse(fh.read(), copy=True, source=path)


def read_itrf_bytes(data):
    """Load an ITRF image already in memory (the worker HELLO fast path):
    arrays are zero-copy read-only views over ``data``."""
    return _parse(data, copy=False)


def inspect_itrf(path) -> dict:
    """Header + section table, without touching array pages."""
    with open(path, "rb") as fh:
        mm = mmap.mmap(fh.fileno(), 0, access=mmap.ACCESS_READ)
        head = _parse_header(mm)
        table = _parse_sections(mm, head["n_sections"])
        return {
            **{k: v for k, v in head.items() if k != "n_sections"},
            "file_bytes": os.path.getsize(path),
            "sections": {
                name: {"dtype": dt, "shape": list(shape),
                       "offset": off, "nbytes": nb}
                for name, (dt, shape, off, nb) in table.items()
            },
        }
