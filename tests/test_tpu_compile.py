"""Compile the CNN-ELM hot path for a described TPU v5e, with no chip.

The TPU compiler is installed with jaxlib and compiles for a topology that
is described and not attached, so these tests catch what interpret mode
cannot: Mosaic refusing a block shape, a kernel without a VJP, a
``pallas_call`` without ``vma`` inside ``shard_map``. Shapes are the
published 6c-2s-12c-2s width at B=200 (and the conv kernel at every
stage of both configs, and at serving's buckets); the stacked executor's
epoch compiles on the one-chip and on the four-chip member mesh. Each
compiled program must hold the Pallas kernels (``tpu_custom_call``), so a
silent fall back to the XLA conv fails here.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every pytest-xdist
worker imports this file. Keep these tests in this one file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs.base import get_config
from repro.core import elm, executor
from repro.kernels.conv2d import ops as conv_ops
from repro.kernels.elm_stats.kernel import elm_stats
from repro.models import cnn

CFG = get_config("cnn_elm_6c12c")
B, K_MEMBERS, NB = 200, 4, 2
F, C = cnn.feature_dim(CFG), CFG.num_classes
# every conv of both configs, (H=W, Cin, Cout, images), in the kernel's
# (C, H, W, B) layout under the training cells' vmap over k=4 members at
# B=200, and serving's buckets of 1 and 32 images for 3c-9c
CONVS = [(28, 1, 3, B), (12, 3, 9, B), (28, 1, 6, B), (12, 6, 12, B),
         (28, 1, 3, 1), (12, 3, 9, 1), (28, 1, 3, 32), (12, 3, 9, 32)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:    # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def compiled_kernels(monkeypatch):
    """The auto policy sees the CPU backend here; steer it to the compiled
    kernels. A chip-less compile cannot be read back from the persistent
    cache, so keep the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _member_params(sharding):
    shapes = jax.eval_shape(lambda: cnn.init_params(CFG, jax.random.PRNGKey(0)))
    return jax.tree.map(lambda a: _sds((K_MEMBERS,) + a.shape, sharding),
                        shapes)


@pytest.mark.parametrize("hw,cin,cout,b", CONVS)
def test_conv_forward_and_vjp_compile(one_chip, hw, cin, cout, b):
    x = _sds((K_MEMBERS, cin, hw, hw, b), one_chip)
    w = _sds((K_MEMBERS, 5, 5, cin, cout), one_chip)

    def conv(a, k):
        return conv_ops.conv2d_valid(a, k, use_pallas=True)

    fwd = jax.jit(jax.vmap(conv)).lower(x, w).compile()
    assert _custom_calls(fwd) == 1
    bwd = jax.jit(jax.vmap(jax.grad(lambda a, k: (conv(a, k) ** 2).sum(),
                                    argnums=(0, 1)))).lower(x, w).compile()
    assert _custom_calls(bwd) == 3      # the forward, dX and dW kernels


@pytest.mark.parametrize("masked", [False, True])
def test_elm_stats_compile(one_chip, masked):
    h, t = _sds((B, F), one_chip), _sds((B, C), one_chip)
    mask = _sds((B,), one_chip) if masked else None
    fn = jax.jit(elm_stats)
    assert _custom_calls(fn.lower(h, t, mask).compile()) == 1


def _member_mesh(topo, chips: int):
    """The member mesh the stacked executor builds over ``chips`` of the
    described chips, and its placements: member dim first, member dim
    second (scan-major batches), replicated."""
    mesh = Mesh(np.asarray(topo.devices[:chips]).reshape(chips), ("pod",),
                axis_types=(AxisType.Auto,))
    return (mesh,) + tuple(NamedSharding(mesh, spec)
                           for spec in (P("pod"), P(None, "pod"), P()))


def _no_collectives(text: str) -> bool:
    return not any(c in text for c in ("all-reduce", "all-gather",
                                       "all-to-all", "collective-permute"))


@pytest.mark.parametrize("chips,masked", [(1, False), (1, True), (4, False)])
def test_stacked_epoch_with_sgd_compiles(topo, chips, masked):
    """Alg. 2 lines 9-14 for k=4 members: features, stats, β solve and the
    SGD backward through the conv kernel, one scan — on the one-chip
    member mesh, and on four chips, one member a chip, ``shard_map``-ed
    with ``check_vma`` on (the kernels need ``vma`` on their out_shapes)
    and free of collectives (members are independent)."""
    mesh, member, batch, repl = _member_mesh(topo, chips)
    stats = elm.ELMStats(_sds((K_MEMBERS, F, F), member),
                         _sds((K_MEMBERS, F, C), member),
                         _sds((K_MEMBERS,), member))
    with jax.set_mesh(mesh):
        lowered = executor._stacked_epoch.lower(
            CFG, _member_params(member), stats,
            _sds((NB, K_MEMBERS, B, 28, 28), batch),
            _sds((NB, K_MEMBERS, B, C), batch),
            _sds((NB, K_MEMBERS), batch), _sds((), repl),
            solve_each_batch=True, use_pallas=True, masked=masked)
    # 2 conv forwards for the stats, 2 for the loss, the backward's dX of
    # conv2 and dW of both (conv1's dX is dead and pruned), 1 elm_stats
    compiled = lowered.compile()
    text = compiled.as_text()
    assert _custom_calls(compiled) >= 7
    # every kernel, the backward ones too, lies under its device scope:
    # the chip's trace names the op-name path of each op it runs
    kernels = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert kernels and all(re.search(r'op_name="[^"]*/(conv2d|elm_stats)/',
                                     ln) for ln in kernels)
    for scope in ("beta_solve", "sgd_update"):
        assert f"({scope})/" in text
    assert _no_collectives(text)


def _slot_rows(n, chips, place):
    """The partitions as ``StackedExecutor._put_partitions`` places them:
    one array of chips·n flat rows per member slot, and the labels."""
    slots = K_MEMBERS // chips
    return (tuple(_sds((chips * n, 28 * 28), place) for _ in range(slots)),
            tuple(_sds((chips * n,), place, jnp.int32)
                  for _ in range(slots)))


@pytest.mark.parametrize("chips", [1, 4])
def test_gathered_epoch_compiles_at_the_elm_cell_size(topo, chips):
    """The device-built epoch at the ELM-only cell's shapes (3c-9c, k=4
    members of 60,000 rows, 300 batches of 200): the gather in front of
    the scan compiles with the kernels, and the partitions, the gathered
    epoch and the scan fit well inside one chip's 16 GB. On four chips
    each chip gathers its own member's batches: no collective moves a
    row."""
    cfg = get_config("cnn_elm_3c9c")
    n, nb = 60000, 300
    f, c = cnn.feature_dim(cfg), cfg.num_classes
    mesh, member, batch, repl = _member_mesh(topo, chips)
    shapes = jax.eval_shape(lambda: cnn.init_params(cfg,
                                                    jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda a: _sds((K_MEMBERS,) + a.shape, member),
                          shapes)
    stats = elm.ELMStats(_sds((K_MEMBERS, f, f), member),
                         _sds((K_MEMBERS, f, c), member),
                         _sds((K_MEMBERS,), member))
    idx = _sds((nb, K_MEMBERS, B), batch, jnp.int32)
    with jax.set_mesh(mesh):
        compiled = executor._stacked_epoch.lower(
            cfg, params, stats, idx, idx, _sds((nb, K_MEMBERS), batch),
            _sds((), repl), solve_each_batch=False, use_pallas=True,
            masked=False, rows=_slot_rows(n, chips, member)).compile()
    assert _custom_calls(compiled) >= 3       # two convs, elm_stats
    assert _no_collectives(compiled.as_text())
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8 << 30


@pytest.mark.parametrize("chips", [1, 4])
def test_sgd_epoch_with_its_step_record_compiles(topo, chips):
    """The SGD cell's epoch (6c-12c, k=4 members of 60,000 rows, 300
    batches of 200, gathered on the device) with its step record: every
    member's params at the start of every step, (300, 4, ...) per leaf,
    written under the ``step_record`` scope; on four chips member-sharded
    on its second dim, one member a chip."""
    n, nb = 60000, 300
    mesh, member, batch, repl = _member_mesh(topo, chips)
    stats = elm.ELMStats(_sds((K_MEMBERS, F, F), member),
                         _sds((K_MEMBERS, F, C), member),
                         _sds((K_MEMBERS,), member))
    idx = _sds((nb, K_MEMBERS, B), batch, jnp.int32)
    with jax.set_mesh(mesh):
        lowered = executor._stacked_epoch.lower(
            CFG, _member_params(member), stats, idx, idx,
            _sds((nb, K_MEMBERS), batch), _sds((), repl),
            solve_each_batch=True, use_pallas=True, masked=False,
            rows=_slot_rows(n, chips, member))
    params, _, record = lowered.out_info
    for p, r in zip(jax.tree.leaves(params), jax.tree.leaves(record)):
        assert r.shape == (nb,) + p.shape
    compiled = lowered.compile()
    text = compiled.as_text()
    assert _custom_calls(compiled) >= 7
    assert re.search(r'op_name="[^"]*/step_record/', text)
    assert _no_collectives(text)
    if chips > 1:
        assert all(s.spec[:2] == (None, "pod") for s in jax.tree.leaves(
            compiled.output_shardings[2]))
    # the rows, the scan and a 9.4 MB record: well inside 16 GB a chip
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8 << 30
