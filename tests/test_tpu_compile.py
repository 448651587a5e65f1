"""Compile the chip path for a described TPU v5e, without a chip.

The TPU compiler is installed even where no TPU is attached: it compiles
for a described ``v5e:2x2`` topology and refuses what the chip would
refuse (64-bit Mosaic types, index maps returning int64 under
``jax_enable_x64``, blocks that overflow VMEM, layouts the kernel and XLA
disagree on). Interpret-mode tests cannot see any of that. Nothing here
runs: each test lowers and compiles at the paper's MD width (n=9,997,
padded by the wrappers to the tile multiple).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under pytest-xdist every worker
imports this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.cholesky import cholesky_blocked
from repro.core.lanczos import _lanczos_segment, default_subspace
from repro.core.looped import solve_upper_looped
from repro.core.operators import SplitC
from repro.core.tridiag_eig import default_tridiag_method
from repro.kernels import dispatch
from repro.kernels.gemm.kernel import gemm_pallas
from repro.kernels.house_panel.kernel import house_panel_pallas
from repro.kernels.house_panel.ops import house_panel, vmem_bytes
from repro.kernels.rot_apply.kernel import rot_apply_pallas
from repro.kernels.symv.kernel import symm_block_pallas
from repro.kernels.symv.ops import symm_block
from repro.kernels.syr2k.kernel import syr2k_pallas
from repro.kernels.tridiag_eig.kernel import bisect_sturm_pallas
from repro.kernels.tridiag_eig.ops import bisect_vmem_bytes, invit_vmem_bytes

N_MD = 9_997
N_PAD = 10_240          # N_MD padded to the 512-row symm tile


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to a persistent cache but
    # can never be read back here; keep any configured cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, *shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# ------------------------------------------------------------- kernels --

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_symm_block_kernel_compiles(one_chip, dtype):
    """KE/KI's fused matvec (symv is its p=1 case, padded to 128 lanes)."""
    c = _compile(lambda A, X: symm_block_pallas(A, X, block=512,
                                                interpret=False),
                 _spec(one_chip, N_PAD, N_PAD, dtype=dtype),
                 _spec(one_chip, N_PAD, 128, dtype=dtype))
    _assert_kernel(c)


def test_rot_apply_kernel_compiles(one_chip):
    """TT2's wavefront rotation: int32 index maps under x64."""
    G, L = 1248, 128
    c = _compile(lambda x0, x1, c, s: rot_apply_pallas(
        x0, x1, c, s, bg=8, bl=128, interpret=False),
        _spec(one_chip, G, L), _spec(one_chip, G, L),
        _spec(one_chip, G, 1), _spec(one_chip, G, 1))
    _assert_kernel(c)


def test_bisect_sturm_kernel_compiles(one_chip):
    N, S = N_MD + (-N_MD) % 8, 128
    col, row = _spec(one_chip, N, 1), _spec(one_chip, 1, S)
    c = _compile(lambda d, e2, ks, lo, hi, piv: bisect_sturm_pallas(
        d, e2, ks, lo, hi, piv, interpret=False),
        col, col, _spec(one_chip, 1, S, dtype=jnp.int32), row, row, row)
    _assert_kernel(c)


def test_house_panel_kernel_compiles_within_budget(one_chip):
    """The tallest panel the dispatch helper sends to the kernel."""
    b = 16
    rows = 8
    while vmem_bytes(2 * rows, b) <= dispatch.VMEM_BUDGET:
        rows *= 2
    assert dispatch.pallas_ok(jnp.float32, vmem_bytes(rows, b))
    c = _compile(lambda E, rs: house_panel_pallas(E, rs, interpret=False),
                 _spec(one_chip, rows, b),
                 _spec(one_chip, 1, dtype=jnp.int32))
    _assert_kernel(c)


def test_gemm_and_syr2k_kernels_compile(one_chip):
    _assert_kernel(_compile(
        lambda A, B: gemm_pallas(A, B, interpret=False),
        _spec(one_chip, 4096, 4096), _spec(one_chip, 4096, 4096)))
    _assert_kernel(_compile(
        lambda C, V, W: syr2k_pallas(C, V, W, interpret=False),
        _spec(one_chip, N_PAD, N_PAD), _spec(one_chip, N_PAD, 16),
        _spec(one_chip, N_PAD, 16)))


# ------------------------------------------------- dispatch decisions --

def test_dispatch_sends_f64_and_over_vmem_shapes_to_xla(monkeypatch):
    """On a TPU: f64 operands and blocks that overflow VMEM take the XLA
    expression, f32 within budget takes the kernel, and nothing runs in
    interpret mode."""
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    assert dispatch.interpret(True) is False
    assert not dispatch.use_pallas(jnp.float64, force=True)
    assert dispatch.use_pallas(jnp.float32)
    assert dispatch.use_pallas(jnp.bfloat16)
    # the MD-width house_panel panel and invit block overflow VMEM; the
    # Sturm columns fit
    assert not dispatch.use_pallas(jnp.float32, vmem_bytes(N_PAD, 16))
    assert not dispatch.use_pallas(jnp.float32, invit_vmem_bytes(N_MD, 100))
    assert dispatch.use_pallas(jnp.float32, bisect_vmem_bytes(N_MD))
    # TT3/TD2 run f64: the batched XLA program, never the kernels
    assert default_tridiag_method(jnp.float64, N_MD, 100) == "batched"
    assert default_tridiag_method(jnp.float32, 512, 64) == "kernel"

    def has_kernel(fn, *args):
        return "pallas_call" in str(jax.make_jaxpr(fn)(*args))

    A64 = jax.ShapeDtypeStruct((256, 256), jnp.float64)
    X64 = jax.ShapeDtypeStruct((256, 4), jnp.float64)
    assert not has_kernel(symm_block, A64, X64)
    A32 = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    X32 = jax.ShapeDtypeStruct((256, 4), jnp.float32)
    assert has_kernel(symm_block, A32, X32)
    tall = jax.ShapeDtypeStruct((N_PAD, 16), jnp.float32)
    assert not has_kernel(lambda E: house_panel(E, 0), tall)


# ---------------------------------------------------- stage programs --

def test_looped_stage_programs_compile_at_md_width(one_chip):
    """GS1 and BT1 as the flat-compile loops of ``core.looped``: XLA's own
    f64 Cholesky did not finish compiling at this n in 400 s. The f64
    emulation's temporaries stay well inside the 16 GB chip."""
    f64 = jnp.float64
    gs1 = _compile(cholesky_blocked, _spec(one_chip, N_MD, N_MD, dtype=f64))
    bt1 = _compile(solve_upper_looped, _spec(one_chip, N_MD, N_MD, dtype=f64),
                   _spec(one_chip, N_MD, 100, dtype=f64))
    for c in (gs1, bt1):
        assert c.memory_analysis().temp_size_in_bytes < 6 * 2**30


_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}
_ARRAY = re.compile(r"\b(" + "|".join(_BYTES) + r")\[([\d,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\((.*)$")


def _array_bytes(shape: str) -> int:
    """Bytes of the largest array in an HLO shape (a tuple's too)."""
    out = 0
    for dtype, dims in _ARRAY.findall(shape):
        size = _BYTES[dtype]
        for d in filter(None, dims.split(",")):
            size *= int(d)
        out = max(out, size)
    return out


def _hlo(text: str):
    """(instructions by computation, computations a fusion calls, the
    shape of every instruction) of a compiled module's text."""
    comps, fused, shapes, cur = {}, set(), {}, None
    for line in text.splitlines():
        if line.endswith("{") and line.startswith(("%", "ENTRY")):
            cur = line.split()[line.startswith("ENTRY")].lstrip("%")
            comps[cur] = []
        elif (m := _INSTR.match(line)) and cur is not None:
            name, shape, opcode, rest = m.groups()
            comps[cur].append((name, shape, opcode, rest))
            shapes[name] = shape
            if opcode == "fusion":
                fused.update(re.findall(r"calls=%([\w.-]+)", rest))
    return comps, fused, shapes


def test_split_krylov_segment_reads_int8_slices_at_md_width(one_chip,
                                                            monkeypatch):
    """KE's segment on the split operator (md-ke: n=9,997, p=4): each block
    step is int8 products into int32 on the slices as they are. No fusion
    of the program writes an array of n^2 bytes, one int8 slice of C: the
    f64 product re-splits each 1,024-row tile of C into f32[8,1024,n]
    pieces, 3.3 n^2 bytes, at every step. The temporaries stay under 1 GB
    (the f64 product's take 2.3 GB)."""
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    n, p = N_MD, 4
    m = default_subspace(100, n, p)
    op = SplitC(_spec(one_chip, n, 8, n, dtype=jnp.int8),
                _spec(one_chip, n, dtype=jnp.float64))
    compiled = _lanczos_segment.lower(
        op, _spec(one_chip, n, m + p, dtype=jnp.float64),
        _spec(one_chip, m + p, m + p, dtype=jnp.float64),
        _spec(one_chip, dtype=jnp.int32), p=p).compile()
    comps, fused, shapes = _hlo(compiled.as_text())
    written = [(_array_bytes(shape), name)
               for comp, instrs in comps.items() if comp not in fused
               for name, shape, opcode, _ in instrs if opcode == "fusion"]
    assert max(written) < (n * n, ""), max(written)
    int8_products = [
        name for instrs in comps.values()
        for name, shape, opcode, rest in instrs
        if opcode == "convolution" and shape.startswith("s32[")
        and all(shapes[a].startswith("s8[")
                for a in re.findall(r"%([\w.-]+)", rest.split(")")[0]))]
    assert int8_products
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30
