"""The program's layer names in a trace (``repro.scopes``): every heavy
op of the compiled epoch programs and of the serving scorer carries a
device scope in its HLO op name, and a profiled Map+Reduce run and a
profiled endpoint leave their host spans, nested as documented, with the
flush spans' arguments adding up to what the server answered."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scopes
from repro.analysis.hlo import _tiny_inputs
from repro.configs.base import get_reduced_config
from repro.core import cnn_elm, elm, executor
from repro.core.averaging import broadcast_member_dim
from repro.core.runner import AveragingRun, MapConfig, ReduceConfig
from repro.data.partition import partition_iid
from repro.data.synthetic import make_extended_mnist
from repro.models import cnn
from repro.optim.schedules import dynamic_paper
from repro.serve import BucketedScorer, EnsembleServer, ServeConfig

CFG = get_reduced_config("cnn_elm_6c12c")
K = 2
HEAVY = re.compile(
    r"= .*?\s(dot|convolution|custom-call|cholesky|triangular-solve)\(")


def _unscoped(hlo_text: str):
    """(op, op name) of each heavy op whose op-name path names no scope
    above the op itself, and the number of heavy ops."""
    bad, n = [], 0
    for line in hlo_text.splitlines():
        m = HEAVY.search(line)
        if not m:
            continue
        n += 1
        op = re.search(r'op_name="([^"]*)"', line)
        path = op.group(1) if op else ""
        above = set(re.split(r"[/()]", path.rsplit("/", 1)[0]))
        if not above & set(scopes.SCOPES):
            bad.append((m.group(1), path))
    return bad, n


@pytest.mark.parametrize("sgd", [False, True], ids=["elm_only", "sgd"])
def test_epoch_program_ops_carry_scopes(sgd):
    params = cnn.init_params(CFG, jax.random.PRNGKey(0))
    F, C = cnn.feature_dim(CFG), CFG.num_classes
    xb, tb, mb = _tiny_inputs(CFG, K, 4, 2)
    text = executor._stacked_epoch.lower(
        CFG, broadcast_member_dim(params, K), elm.zero_stats_stacked(K, F, C),
        jnp.asarray(xb), jnp.asarray(tb), jnp.asarray(mb), jnp.float32(0.1),
        solve_each_batch=sgd, use_pallas=False,
        masked=False).compile().as_text()
    bad, n = _unscoped(text)
    assert n >= (8 if sgd else 3) and bad == []
    for scope in ((scopes.CONV2D, scopes.ELM_STATS)
                  + ((scopes.BETA_SOLVE, scopes.SGD_UPDATE) if sgd else ())):
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)]', text), scope


@pytest.mark.parametrize("sgd", [False, True], ids=["elm_only", "sgd"])
def test_gathered_epoch_ops_carry_scopes(sgd):
    """The device-built epoch: the gather of the batches sits under its
    own scope in front of the scan, never under ``conv2d`` (whose device
    time the conv roofline reads), and the scan's ops keep theirs."""
    params = cnn.init_params(CFG, jax.random.PRNGKey(0))
    F, C = cnn.feature_dim(CFG), CFG.num_classes
    n, nb, B = 40, 2, 4
    xs = tuple(jnp.zeros((n, CFG.image_size * CFG.image_size))
               for _ in range(K))
    ys = tuple(jnp.zeros((n,), jnp.int32) for _ in range(K))
    idx = jnp.zeros((nb, K, B), jnp.int32)
    text = executor._stacked_epoch.lower(
        CFG, broadcast_member_dim(params, K), elm.zero_stats_stacked(K, F, C),
        idx, idx, jnp.ones((nb, K)), jnp.float32(0.1), solve_each_batch=sgd,
        use_pallas=False, masked=True, rows=(xs, ys)).compile().as_text()
    bad, n_heavy = _unscoped(text)
    assert n_heavy >= (8 if sgd else 3) and bad == []
    gathers = re.findall(r' gather\(.*?op_name="([^"]*)"', text)
    assert gathers and all(
        scopes.EPOCH_GATHER in p and scopes.CONV2D not in p for p in gathers)
    for scope in ((scopes.EPOCH_GATHER, scopes.CONV2D, scopes.ELM_STATS)
                  + ((scopes.BETA_SOLVE, scopes.SGD_UPDATE) if sgd else ())):
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)]', text), scope


def test_scorer_ops_carry_scopes():
    params = cnn.init_params(CFG, jax.random.PRNGKey(0))
    members = cnn_elm.StackedMembers(
        broadcast_member_dim(params, K),
        jnp.zeros((K, cnn.feature_dim(CFG), CFG.num_classes)))
    scorer = BucketedScorer(CFG, members, max_batch=4)
    text = scorer._fn.lower(
        members.cnn_params, members.beta,
        jnp.zeros((4, CFG.image_size, CFG.image_size))).compile().as_text()
    bad, n = _unscoped(text)
    assert n >= 3 and bad == []
    assert re.search(rf'op_name="[^"]*[/(]{scopes.READOUT}[/)]', text)


def _host_spans(trace_dir):
    """{(plane, line index): [(name, start, end, args)]} of the repro spans
    in the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.setdefault((plane.name, i), []).append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                         {k: v for k, v in ev.stats}))
    return out


def _inside(child, parents):
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def test_profiled_run_and_endpoint_leave_spans(tmp_path):
    ds = make_extended_mnist(n_per_class=10, seed=0)
    parts = partition_iid(ds.x, ds.y, K, seed=0)
    run = AveragingRun(CFG, MapConfig(epochs=2, batch_size=20,
                                      lr_schedule=dynamic_paper(0.05),
                                      backend="stacked", chunk_batches=2),
                       ReduceConfig(rounds=2))
    images = np.asarray(ds.x[:7], np.float32)
    with jax.profiler.trace(str(tmp_path)):
        res = run.run(parts, jax.random.PRNGKey(0))
        jax.block_until_ready(res.averaged.beta)
        scorer = res.ensemble().bucketed_scorer(max_batch=4)
        with EnsembleServer(scorer, ServeConfig(max_batch=4,
                                                max_wait_ms=2.0)) as server:
            answers = [f.result(timeout=60)
                       for f in server.submit_many(images)]
        stats = server.stats()
    assert len(answers) == stats.completed == len(images)

    by_line = _host_spans(tmp_path)
    spans = [s for line in by_line.values() for s in line]
    names = {s[0] for s in spans}
    assert names == set(scopes.SPANS)
    count = {n: sum(s[0] == n for s in spans) for n in names}
    # two rounds of one epoch, each built once and put in chunks of 2,
    # after one upload of the partitions (the device gathers the epochs)
    assert count[scopes.MAP_EPOCH_BUILD] == 2
    assert count[scopes.MAP_PUT] == count[scopes.MAP_DISPATCH] + 1
    assert count[scopes.MAP_DISPATCH] >= 2
    # one inter-round sync and the final averaged model
    assert count[scopes.MAP_REDUCE] == 2

    flushes = [s for s in spans if s[0] == scopes.SERVE_FLUSH]
    for line in by_line.values():
        named = lambda n: [s for s in line if s[0] == n]
        # one score call under every flush, dispatch and fetch under a score
        for f in named(scopes.SERVE_FLUSH):
            assert sum(_inside(s, [f])
                       for s in named(scopes.SERVE_SCORE)) == 1
        for n in (scopes.SERVE_DISPATCH, scopes.SERVE_FETCH):
            assert all(_inside(s, named(scopes.SERVE_SCORE))
                       for s in named(n))
        # a flush begins where its batch's collection ended
        for c in named(scopes.SERVE_COLLECT):
            assert any(f[1] >= c[2] for f in named(scopes.SERVE_FLUSH))
    assert sum(int(f[3]["n"]) for f in flushes) == stats.completed
    assert len(flushes) == stats.batches
    assert all(f[3]["wait_us"] >= 0 and f[3]["bucket"] >= f[3]["n"]
               for f in flushes)
