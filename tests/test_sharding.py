"""Sharding resolver + logical-axis consistency across all architectures."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import ARCH_IDS, get_config, get_reduced_config
from repro.distributed import sharding
from repro.models import api
from repro.launch.mesh import auto_mesh

LM_ARCHS = [a for a in ARCH_IDS if not a.startswith("cnn_elm")]


class FakeMesh:
    """Stand-in with just .shape — resolve_spec only reads mesh.shape."""

    def __init__(self, **axes):
        self.shape = axes


MESH = FakeMesh(data=16, model=16)
PODMESH = FakeMesh(pod=2, data=16, model=16)


def test_basic_resolution():
    spec = sharding.resolve_spec((1024, 4096), ("vocab", "embed"), MESH)
    assert spec == P("model", None)


def test_divisibility_fallback():
    # 122753 (minicpm vocab) % 16 != 0 -> replicate
    spec = sharding.resolve_spec((122753, 2304), ("vocab", "embed"), MESH)
    assert spec == P(None, None)


def test_no_axis_reuse_within_array():
    # both dims want 'model': only the first gets it
    spec = sharding.resolve_spec((128, 256), ("expert", "ff"), MESH)
    assert spec == P("model", None)


def test_tuple_axis_candidates():
    rules = {"batch": (("pod", "data"), "data")}
    spec = sharding.resolve_spec((128, 1), ("batch", None), PODMESH, rules)
    assert spec == P(("pod", "data"), None)
    # batch=8 not divisible by 32 -> falls back to data axis
    spec = sharding.resolve_spec((16, 1), ("batch", None), PODMESH, rules)
    assert spec == P("data", None)


def test_member_dim_prepend():
    tree = {"w": ("embed", "ff")}
    out = sharding.with_member_dim(tree)
    assert out == {"w": ("member", "embed", "ff")}


def test_member_resolve_rules():
    """The 'member' logical axis: resolves to 'pod' when it divides, falls
    back to replication when it doesn't or the mesh has no pod axis, and
    honours custom rules — the divisibility contract the mesh executor's
    pad-to-a-pod-multiple step relies on (k_pad always divides, so the
    fallback never fires there)."""
    pod8 = FakeMesh(pod=8)
    assert sharding.resolve_spec((8, 5), ("member", None), pod8) == \
        P("pod", None)
    assert sharding.resolve_spec((16,), ("member",), pod8) == P("pod")
    # 6 % 8 != 0 -> replicate (exactly why the stacked executor pads 6 -> 8)
    assert sharding.resolve_spec((6, 5), ("member", None), pod8) == \
        P(None, None)
    assert sharding.resolve_spec((8, 5), ("member", None), MESH) == \
        P(None, None)  # no pod axis at all
    # custom rules can re-home the member dim (32 divides data=16)
    assert sharding.resolve_spec((32, 5), ("member", None), MESH,
                                 rules={"member": ("data",)}) == \
        P("data", None)


def test_member_and_batch_specs_match_shardings():
    """The spec-level twin (shard_map in/out_specs) must agree exactly
    with ``member_dim_shardings``."""
    mesh = auto_mesh((1,), ("pod",))
    tree = {"w": jnp.zeros((4, 5, 3)), "b": jnp.zeros((4,))}
    specs = sharding.member_dim_specs(tree, mesh)
    shardings_ = sharding.member_dim_shardings(tree, mesh)
    assert specs == {"w": P("pod", None, None), "b": P("pod")}
    assert jax.tree.map(lambda s: s.spec, shardings_,
                        is_leaf=lambda x: hasattr(x, "spec")) == specs


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_logical_tree_matches_param_tree(arch):
    """Every param leaf must have a logical spec of matching rank."""
    cfg = get_reduced_config(arch)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    logical = api.logical_axes(cfg)
    jax.tree.map(
        lambda a, log: (_ for _ in ()).throw(
            AssertionError(f"{arch}: {a.shape} vs {log}"))
        if a.ndim != len(log) else None,
        params, logical,
        is_leaf=lambda x: isinstance(x, tuple) and not isinstance(x, dict))


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_full_config_params_shard_meaningfully(arch):
    """On the production mesh, the big 2D+ weights of the FULL config must
    actually shard (not silently replicate) — at least 50% of param bytes."""
    cfg = get_config(arch)
    params = jax.eval_shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0)))
    logical = api.logical_axes(cfg)
    total, sharded = 0, 0
    for s, log in zip(jax.tree.leaves(params),
                      jax.tree.leaves(logical,
                                      is_leaf=lambda x: isinstance(x, tuple)
                                      and all(e is None or isinstance(e, str)
                                              for e in x))):
        nbytes = np.prod(s.shape) * s.dtype.itemsize
        total += nbytes
        spec = sharding.resolve_spec(s.shape, log, MESH)
        if any(a is not None for a in spec):
            sharded += nbytes
    assert sharded / total > 0.5, f"{arch}: only {sharded/total:.0%} sharded"


def test_cache_logical_matches_cache_tree():
    for arch in LM_ARCHS:
        cfg = get_reduced_config(arch)
        if cfg.is_encoder_only:
            continue
        cache = jax.eval_shape(lambda c=cfg: api.init_cache(c, 4, 32))
        logical = api.cache_logical(cfg)
        jax.tree.map(
            lambda a, log: (_ for _ in ()).throw(AssertionError(arch))
            if a.ndim != len(log) else None,
            cache, logical,
            is_leaf=lambda x: isinstance(x, tuple) and not isinstance(x, dict))
