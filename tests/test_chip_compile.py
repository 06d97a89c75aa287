"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler installed here compiles for a chip that
is described, not attached, and refuses what the chip's compiler would
refuse (Mosaic crashes, scoped-VMEM overruns, unaligned slices) — which
interpret-mode tests cannot see. The topology is described inside a
fixture, never at import time: only one process may load the TPU
library, and every xdist worker imports this file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from jepsen_tpu.lin import dense_pallas, psort, psort_fused
from jepsen_tpu.lin.dense import MAX_DENSE_STATES

# The headline 100k-op history's window (dense plan width 13).
HEADLINE_W = 13


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    # The psort kernels pick interpret mode from the backend JAX sees,
    # which here is the CPU; the described chip needs the real kernel.
    monkeypatch.setattr(psort, "_interpret", lambda: False)
    monkeypatch.setattr(psort_fused, "_interpret", lambda: False)


def _shape(chip, shape, dtype=jnp.uint32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


# Compiled text by kernel and shape: the name checks below read what
# the compile tests already built instead of compiling it again.
_TEXT: dict = {}


def _compile(key, lower):
    if key not in _TEXT:
        lowered = lower()
        # A Pallas TPU kernel lowers to a Mosaic custom call; the
        # interpreter would lower to plain HLO and prove nothing.
        assert "tpu_custom_call" in lowered.as_text()
        _TEXT[key] = lowered.compile().as_text()
    return _TEXT[key]


def _dense(chip, w, ns):
    chunk = 512
    return _compile(("dense", w, ns), lambda: dense_pallas.pallas_chunk.lower(
        _shape(chip, (1 << w,)), _shape(chip, (), jnp.int32),
        _shape(chip, (w, ns, chunk)), _shape(chip, (chunk,), jnp.int32),
        w=w, ns=ns, chunk=chunk))


def _fused(chip, pair):
    cap, m, b = 1 << 10, 7, 6
    return _compile(("fused", pair), lambda: psort_fused._fixpoint_call.lower(
        _shape(chip, (cap,)), _shape(chip, (cap,)) if pair else None,
        _shape(chip, (), jnp.int32),
        _shape(chip, (psort_fused.N_COL_ROWS, m)),
        _shape(chip, (2, 1 << b)), cap=cap, b=b, it_max=64, pair=pair,
        M=m))


# Each psort kernel by its explicit name, at a small size (the dedup at
# the size its compile test has always used).
_N = 1 << 10
_PSORT = {
    "psort_dedup": lambda c: psort._dedup_call.lower(
        _shape(c, (1 << 14,)), n_pad=1 << 14),
    "psort_dedup2": lambda c: psort._dedup2_call.lower(
        _shape(c, (_N,)), _shape(c, (_N,)), n_pad=_N),
    "psort_dedup_dom": lambda c: psort._dedup_dom_call.lower(
        _shape(c, (_N,)), _shape(c, (_N,)), _shape(c, ()), _shape(c, ()),
        n_pad=_N),
    "psort_dedup2_dom": lambda c: psort._dedup2_dom_call.lower(
        *[_shape(c, (_N,))] * 4, _shape(c, (4,)), n_pad=_N),
    "psort_compact": lambda c: psort._compact_call.lower(
        _shape(c, (_N,)), n_pad=_N),
    "psort_compact2": lambda c: psort._compact2_call.lower(
        _shape(c, (_N,)), _shape(c, (_N,)), n_pad=_N),
}


def _psort(chip, name):
    return _compile((name,), lambda: _PSORT[name](chip))


@pytest.mark.parametrize("w,ns,row_block", [
    (HEADLINE_W, 8, None), (dense_pallas.MAX_PALLAS_W, 4, None),
    (10, MAX_DENSE_STATES, 2 * dense_pallas.ROW_BLOCK)],
    ids=["13-8", "18-4", "10-32"])
def test_dense_pallas_chunk_compiles(chip, monkeypatch, w, ns, row_block):
    # MAX_PALLAS_W at the smallest state bucket: the kernel's compile
    # time grows with w * ns, and the width is what the bound is about.
    # (10, 32) at twice ROW_BLOCK rows a step holds more words of masks
    # in SMEM than the largest block any width takes (MAX_PALLAS_W x 32
    # states x ROW_BLOCK), and compiles in seconds: an overrun fails.
    if row_block:
        assert w * ns * row_block > \
            dense_pallas.MAX_PALLAS_W * MAX_DENSE_STATES * \
            dense_pallas.ROW_BLOCK
        monkeypatch.setattr(dense_pallas, "ROW_BLOCK", row_block)
    _dense(chip, w, ns)


def test_psort_dedup_compiles(chip, mosaic):
    _psort(chip, "psort_dedup")


@pytest.mark.parametrize("pair", [False, True])
def test_fused_fixpoint_compiles(chip, mosaic, pair):
    _fused(chip, pair)


def _custom_calls(text):
    """Instruction names of the compiled program's Mosaic calls: the
    names a profile's device ops carry."""
    return [m.group(1) for m in
            re.finditer(r"%([\w.\-]+) = [^\n]*custom-call", text)]


def _named(text, name):
    return any(re.fullmatch(rf"{re.escape(name)}(\.\d+)?", c)
               for c in _custom_calls(text))


def test_dense_kernel_carries_its_name(chip):
    text = _dense(chip, HEADLINE_W, 8)
    assert _named(text, dense_pallas.KERNEL_NAME), _custom_calls(text)
    # The benchmark finds the dense kernel's device time by this
    # substring of the op's name.
    assert "pallas_chunk" in dense_pallas.KERNEL_NAME


@pytest.mark.parametrize("name", sorted(_PSORT) + ["psort_fused_fixpoint"])
def test_psort_kernels_carry_their_names(chip, mosaic, name):
    text = _fused(chip, False) if name == "psort_fused_fixpoint" \
        else _psort(chip, name)
    assert _named(text, name), _custom_calls(text)
