"""Compile the served path's device programs for a TPU v5e, without a chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology and raises what the chip's compiler
would raise (a block shape off the tiling, a kernel over the scoped-VMEM
limit, a program that does not fit HBM).  Interpret-mode tests cannot see
any of that.  Here: the fused snapshot capture/restore kernels at every
cache family's published widths with 2048-token rows, and the qwen2-1.5b
decode step at the on-chip smoke's arena shape (``chip_smoke.py``), which
must store its new tokens into the stacked KV cache in place.

Nothing runs, so nothing here is a time or a result.  The topology is
described inside a fixture, never at import: only one process may load the
TPU library, and every pytest worker imports this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels import kv_snapshot
from repro.models import model as M

ROWS, TOKENS = 64, 2048        # chip_smoke.py: 64 partitions x 2048 tokens
HBM_BYTES = 16 * 2**30         # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # these compiles target a chip this process cannot run on: keep them
    # out of any persistent compilation cache the environment names
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)


def _on(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("direction", ["capture", "restore"])
@pytest.mark.parametrize("arch", ["qwen2-1.5b", "mamba2-780m",
                                  "recurrentgemma-2b"])
def test_snapshot_kernel_compiles_for_v5e(one_chip, arch, direction):
    """One row of a 64-row arena at 2048 tokens, compiled (not interpreted)
    for the chip: the kernel is in the program, and nothing arena-sized is
    copied or staged around it (temporaries stay under one row)."""
    caches = M.abstract_caches(get_config(arch), ROWS, TOKENS)
    leaves, axes, _ = M.cache_flat_axes(caches)
    layout = kv_snapshot.build_layout(leaves, axes)
    leaves = tuple(_on(leaves, one_chip))
    rows = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    if direction == "capture":
        compiled = jax.jit(lambda lv, r: kv_snapshot.snapshot_capture(
            lv, r, layout=layout, interpret=False)).lower(
                leaves, rows).compile()
    else:
        blob = jax.ShapeDtypeStruct((1, layout.total_elems),
                                    jnp.dtype(layout.dtype),
                                    sharding=one_chip)
        compiled = jax.jit(lambda lv, b, r: kv_snapshot.snapshot_restore(
            lv, b, r, layout=layout, interpret=False),
            donate_argnums=(0,)).lower(leaves, blob, rows).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= layout.row_bytes, \
        (mem.temp_size_in_bytes, layout.row_bytes)


DECODE_CFG = get_config("qwen2-1.5b")


@pytest.fixture(scope="module")
def decode_compiled(one_chip):
    """qwen2-1.5b decode over the smoke's largest arena bucket, with the
    arena donated as the engine donates it."""
    cfg = DECODE_CFG
    params = _on(M.abstract_params(cfg), one_chip)
    caches = _on(M.abstract_caches(cfg, ROWS, TOKENS), one_chip)
    toks = jax.ShapeDtypeStruct((ROWS, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    return jax.jit(
        lambda p, t, po, c: M.decode_step(cfg, p, t, po, c),
        donate_argnums=(3,)).lower(params, toks, pos, caches).compile()


def test_decode_step_compiles_for_v5e_at_smoke_arena(decode_compiled):
    """qwen2-1.5b decode over the smoke's largest arena bucket fits one
    chip's HBM with its weights."""
    mem = decode_compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def _top_level_instructions(hlo: str):
    """Instruction lines of every computation that is not a fusion's body:
    what these produce is materialized in HBM."""
    fused = set(re.findall(r"kind=k\w+, calls=(%[\w.\-]+)", hlo))
    out, comp = [], None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) .*\{$", line)
        if head:
            comp = head.group(1)
        elif line.startswith("}"):
            comp = None
        elif comp is not None and comp not in fused and " = " in line:
            out.append(line.strip())
    return out


def test_decode_step_writes_kv_in_place_for_v5e(decode_compiled):
    """Decode stores each new token into the stacked KV cache in place:
    no layer's (rows, tokens, kv heads, head dim) slab is sliced out of
    the stack into HBM, copied, or written back into it, and the step's
    temporaries stay under one such slab.  Attention's read of its layer
    may stage the slab in the alternate memory space ``S(1)``."""
    cfg = DECODE_CFG
    slab = (ROWS, TOKENS, cfg.num_kv_heads, cfg.head_dim)
    shapes = {"bf16[%s]" % ",".join(map(str, s))
              for s in (slab, (1,) + slab, (cfg.n_groups,) + slab)}
    moved = []
    for line in _top_level_instructions(decode_compiled.as_text()):
        m = re.match(r"(?:ROOT )?%([\w.\-]+) = (bf16\[[\d,]+\])\{(.*?)\} "
                     r"([\w\-]+)\(", line)
        if m and m.group(2) in shapes and "S(1)" not in m.group(3) and any(
                op in (m.group(1) + " " + m.group(4)) for op in
                ("dynamic-slice", "dynamic-update-slice", "copy")):
            moved.append(line[:160])
    assert not moved, moved
    slab_bytes = 2 * ROWS * TOKENS * cfg.num_kv_heads * cfg.head_dim
    temp = decode_compiled.memory_analysis().temp_size_in_bytes
    assert temp < slab_bytes, (temp, slab_bytes)
