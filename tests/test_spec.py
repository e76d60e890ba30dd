"""EngineSpec: parse / canonical round-trip, dict serialization, and the
loose-kwargs deprecation shim."""
import warnings

import pytest

from repro.serve.spec import MODES, EngineSpec


# ---------------------------------------------------------------------------
# parsing + canonical form
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("text", [
    "integer",
    "flint:reference",
    "integer:bitvector@leaf_major+tree_parallel:4",
    "flint:reference+remote_tree_parallel:2",
    "integer:native_c_table?block_rows=8",
    "integer:reference+auto:3",
    "integer:reference|native_c+tree_parallel:2",
    "integer:pallas@leaf_major",
])
def test_parse_canonical_roundtrip(text):
    spec = EngineSpec.parse(text, validate=False)
    again = EngineSpec.parse(spec.canonical(), validate=False)
    assert again == spec
    # canonical is a fixed point
    assert again.canonical() == spec.canonical()


def test_parse_fields():
    s = EngineSpec.parse("integer:bitvector@leaf_major+tree_parallel:4",
                         validate=False)
    assert (s.mode, s.backend, s.layout) == ("integer", "bitvector", "leaf_major")
    assert (s.plan, s.shards) == ("tree_parallel", 4)


def test_bare_mode_and_bare_backend():
    for m in MODES:
        s = EngineSpec.parse(m, validate=False)
        assert s.mode == m and s.backend == "reference"
    s = EngineSpec.parse("bitvector", validate=False)
    assert s.mode == "integer" and s.backend == "bitvector"


def test_hetero_backends_parse_as_tuple():
    s = EngineSpec.parse("flint:reference|native_c+tree_parallel",
                         validate=False)
    assert s.backend == ("reference", "native_c")
    assert "|" in s.canonical()


def test_auto_shards_renders_auto():
    s = EngineSpec(shards=3)
    assert "+auto:3" in s.canonical()
    assert EngineSpec.parse(s.canonical(), validate=False) == s


def test_query_literals():
    s = EngineSpec.parse(
        "integer:native_c_table?block_rows=8,impl=jit,scale=0.5",
        validate=False)
    assert s.backend_kwargs == {"block_rows": 8, "impl": "jit", "scale": 0.5}


def test_retired_autotune_key_is_refused_in_the_string_form():
    """A spec string still naming ``autotune`` fails with the key named and
    never hands it to a backend's constructor as a kwarg."""
    for text in ("integer:pallas@leaf_major?autotune=true",
                 "integer:native_c_table?block_rows=8,autotune=1"):
        with pytest.raises(ValueError, match="autotune"):
            EngineSpec.parse(text, validate=False)


def test_retired_autotune_key_is_refused_in_dict_and_keyword_forms():
    with pytest.raises(ValueError, match="autotune"):
        EngineSpec.from_dict({"mode": "integer", "backend": "pallas",
                              "autotune": True})
    with pytest.raises(ValueError, match="autotune"):
        EngineSpec.coerce({"backend": "pallas", "autotune": False})
    with pytest.raises(ValueError, match="autotune"):
        EngineSpec.coerce(None, caller="test", backend="pallas",
                          autotune=True)
    with pytest.raises(ValueError, match="autotune"):
        EngineSpec(backend="pallas", backend_kwargs={"autotune": True})


def test_parse_errors():
    with pytest.raises(ValueError):
        EngineSpec.parse("integer:bitvector+tree_parallel:zero", validate=False)
    with pytest.raises(ValueError):
        EngineSpec.parse("integer:reference?keyonly", validate=False)
    with pytest.raises(ValueError):
        EngineSpec.parse("nosuchmode:nosuchbackend@x@y", validate=False)


def test_validate_rejects_unknown_names():
    with pytest.raises(ValueError):
        EngineSpec.parse("integer:nosuchbackend")
    with pytest.raises(ValueError):
        EngineSpec(plan="nosuchplan").validate()
    # a real route validates clean
    EngineSpec.parse("integer:reference+tree_parallel:2")


# ---------------------------------------------------------------------------
# dict round-trip (the wire-handshake serialization)
# ---------------------------------------------------------------------------

def test_dict_roundtrip():
    s = EngineSpec.parse("flint:reference|native_c+tree_parallel:2"
                         "?block_rows=4", validate=False)
    d = s.to_dict()
    assert isinstance(d, dict)
    import json
    assert EngineSpec.from_dict(json.loads(json.dumps(d))) == s


def test_from_dict_rejects_unknown_keys():
    with pytest.raises(ValueError):
        EngineSpec.from_dict({"mode": "integer", "bogus": 1})


def test_replace():
    s = EngineSpec.parse("integer:reference", validate=False)
    assert s.replace(shards=2).shards == 2
    assert s.shards is None  # frozen original untouched


# ---------------------------------------------------------------------------
# coerce: the deprecation shim
# ---------------------------------------------------------------------------

def test_coerce_passthrough_and_string():
    s = EngineSpec(mode="flint")
    assert EngineSpec.coerce(s, caller="t0") is s
    assert EngineSpec.coerce("flint:reference", caller="t1").mode == "flint"
    assert EngineSpec.coerce({"mode": "flint"}, caller="t2").mode == "flint"
    assert EngineSpec.coerce(None, caller="t3") == EngineSpec()


def test_coerce_loose_kwargs_warn_once_per_caller():
    with warnings.catch_warnings(record=True) as seen:
        warnings.simplefilter("always")
        a = EngineSpec.coerce(None, caller="t-warn", mode="flint", shards=2)
        b = EngineSpec.coerce(None, caller="t-warn", mode="integer")
    assert a.mode == "flint" and a.shards == 2
    assert b.mode == "integer"
    deps = [w for w in seen if issubclass(w.category, DeprecationWarning)]
    assert len(deps) == 1  # second call from the same caller is silent


def test_coerce_rejects_spec_plus_loose():
    with pytest.raises(ValueError):
        EngineSpec.coerce("integer:reference", caller="t-mix", shards=2)
