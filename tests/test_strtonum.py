"""Numeric parity pins for the native float conversion (strtonum.h).

The native scanners (``parse.cc``, the engine every text cell runs) lean
on the branch-light SWAR number parser for every label/value they emit;
these tests pin its float conversion against Python ``float()`` on the
edge cases where a hand-rolled parser classically drifts — exponent
overflow/underflow, leading ``+``, inf/nan spellings, trailing garbage,
17-digit round-trips — so the hot path can never silently diverge from the
Python engine's numpy conversion. Comparison is at float32 (the dtype
every parsed value lands in; strtonum's documented contract is that its
<= 2-ulp double error vanishes in the float32 cast).
"""

import numpy as np
import pytest

from dmlc_tpu import native
from dmlc_tpu.utils.check import DMLCError

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native core unavailable")


def _native_value(token: str) -> np.float32:
    """Parse ``token`` as the one feature value of a one-row libsvm
    chunk through the native libsvm scanner; returns the float32 it
    emitted."""
    out = native.parse_libsvm(f"1 1:{token}\n".encode())
    assert len(out["label"]) == 1
    value = out["value"]
    assert value is not None and len(value) == 1, token
    return value[0]


GOLDEN_TOKENS = [
    # exponent overflow -> inf (float('1e400') == inf, no exception)
    "1e400", "-1e400", "1.7976931348623157e308", "3.4028236e38",
    # underflow -> denormal-then-zero at float32
    "1e-400", "4.9e-324", "2.2250738585072014e-308", "1e-46",
    # leading '+' (both sign spellings)
    "+3.5", "+0.5", "+0", "+1e3",
    # inf / nan spellings (strtod and float() both accept these)
    "inf", "-inf", "Infinity", "-Infinity", "INF", "nan", "NaN", "-nan",
    # float32 boundary / precision shapes
    "3.4028235e38", "-3.4028235e38", "16777217", "0.1",
    "0.30000000000000004", "123456789.123456789", "9007199254740993",
    # power-table edges (strtonum's exact-pow10 window is [-22, 22])
    "1e22", "1e23", "1e-22", "1e-23", "2.5e-1",
    # grammar corners
    ".5", "5.", "0075", "-0", "1e+5", "1E5", "1e05",
]


@pytest.mark.parametrize("token", GOLDEN_TOKENS)
def test_native_float_matches_python_float(token):
    got = _native_value(token)
    with np.errstate(over="ignore"):  # overflow-to-inf cast is the point
        want = np.float32(float(token))
    if np.isnan(want):
        assert np.isnan(got), token
    else:
        # exact float32 equality, signed zero included
        assert got == want and np.signbit(got) == np.signbit(want), (
            token, got, want)


@pytest.mark.parametrize("token", ["1.5abc", "3..5", "1e", "2e+", "0x10",
                                   "--1", "1.2.3"])
def test_trailing_garbage_errors(token):
    """Malformed numeric tokens must error loudly (the Python engine
    raises on the same inputs) — silent truncation would let the two
    engines emit different streams from the same bytes."""
    with pytest.raises(DMLCError):
        _native_value(token)


def test_17_digit_round_trip():
    """repr(float) emits <= 17 significant digits that round-trip to the
    same double; parsing that string natively must land on the same
    float32 as float() for a deterministic sweep of magnitudes."""
    rng = np.random.default_rng(1234)
    for _ in range(200):
        d = float(rng.standard_normal() * 10.0 ** rng.integers(-30, 30))
        token = repr(d)
        got = _native_value(token)
        want = np.float32(float(token))
        assert got == want, (token, got, want)


def test_engine_parity_on_edge_corpus(tmp_path):
    """The drift pin at engine level: a corpus made of the golden edge
    tokens parses byte-identically through the native and the Python
    engine (labels use a plain index so rows never get skipped)."""
    from dmlc_tpu.data import create_parser

    finite = [t for t in GOLDEN_TOKENS if not np.isnan(float(t))]
    lines = [f"{i % 2} 1:{t} 2:{t}" for i, t in enumerate(finite)]
    p = tmp_path / "edge.libsvm"
    p.write_text("\n".join(lines) + "\n")

    def drain(engine):
        parser = create_parser(str(p), 0, 1, "libsvm", threaded=True,
                               parse_workers=1, engine=engine)
        try:
            vals = []
            while (b := parser.next_block()) is not None:
                vals.append(np.asarray(b.value))
            return np.concatenate(vals)
        finally:
            parser.close()

    np.testing.assert_array_equal(drain("native"), drain("python"))


def test_property_random_floats():
    """Property sweep (hypothesis when present, seeded numpy fallback):
    any finite float formatted via repr or positional/exponent formats
    parses to the identical float32."""
    hypothesis = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hypothesis.given(st.floats(allow_nan=False, allow_infinity=False),
                      st.sampled_from(["r", ".6f", ".3e", ".17g"]))
    @hypothesis.settings(max_examples=300, deadline=None)
    def check(d, spec):
        token = repr(d) if spec == "r" else format(d, spec)
        got = _native_value(token)
        want = np.float32(float(token))
        if np.isnan(want):  # huge .6f strings can overflow to inf, not nan
            assert np.isnan(got)
        else:
            assert got == want, (token, got, want)

    check()
