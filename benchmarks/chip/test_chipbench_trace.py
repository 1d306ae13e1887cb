"""Trace reduction on hand-built events: busy union, idle gaps and what
the host did in them, attribution by op-name path, exposed collective
time."""
from chipbench import trace as tr

DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
OPS = "XLA Ops"


def op(plane, name, path, start, dur):
    return tr.Event(plane, OPS, name, path, float(start), float(dur))


def host(name, start, dur, line="python3"):
    return tr.Event(HOST, line, name, "", float(start), float(dur))


def hand_events():
    conv = "jit(_stacked_epoch)/while/body/vmap(jit(_conv2d_valid))/dot"
    stats = "jit(_stacked_epoch)/while/body/jit(_elm_stats)/pallas_call"
    solve = "jit(_stacked_epoch)/while/body/cholesky"
    return [
        host("bench.window", 0, 1000),
        host("bench.job", 0, 600),
        host("PjitFunction(_stacked_epoch)", 50, 40),
        host("device_put", 300, 200),
        # chip 0: ops overlap at 100-150, idle 400-500 and 900-1000
        op(DEV0, "fusion.1", conv, 100, 100),
        op(DEV0, "custom-call.2", stats, 150, 100),
        op(DEV0, "fusion.3", solve, 250, 150),
        op(DEV0, "fusion.4", "jit(_stacked_epoch)/add", 500, 400),
        # chip 1: an all-reduce half hidden under compute
        op(DEV1, "all-reduce.7", "jit(_mesh_reduce)/psum", 0, 200),
        op(DEV1, "fusion.8", "jit(_mesh_reduce)/mul", 100, 300),
        # outside the window: clipped away
        op(DEV0, "fusion.9", conv, 2000, 500),
        # not an op line: ignored
        tr.Event(DEV0, "XLA Modules", "jit_f", "", 0.0, 1000.0),
    ]


def test_union_and_gaps():
    cover = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert cover == [(0, 3), (5, 8)]
    assert tr.length(cover) == 6
    assert list(tr.gaps(cover, -1, 10)) == [(-1, 0), (3, 5), (8, 10)]
    assert list(tr.clip([(0, 5), (8, 9)], 2, 8.5)) == [(2, 5), (8, 8.5)]


def test_attribution_by_op_path():
    ev = hand_events()
    assert tr.layer_of(ev[4]) == "conv2d"
    assert tr.layer_of(ev[5]) == "elm_stats"
    assert tr.layer_of(ev[6]) == "beta_solve"
    assert tr.layer_of(ev[7]) is None
    assert tr.layer_of(ev[8]) == "collective"


def test_summary_busy_idle_layers():
    ev = hand_events()
    s = tr.summarize(ev, tr.window_of(ev, "bench.window"))
    assert s.window_s == 1000 / 1e9
    assert s.busy_s == {0: 700 / 1e9, 1: 400 / 1e9}
    assert s.chips == 2
    assert s.mean_busy_s() == 550 / 1e9
    assert s.layer_s[0] == {"conv2d": 100 / 1e9, "elm_stats": 100 / 1e9,
                            "beta_solve": 150 / 1e9}
    assert s.total_layer_s("collective") == 200 / 1e9
    # chip 1's all-reduce runs 0-200, compute 100-400: 100 ns exposed
    assert s.exposed_collective_s == {0: 0.0, 1: 100 / 1e9}
    # chip 0 idles 0-100 (the dispatch), 400-500 (device_put, inside
    # the job), 900-1000 (the window alone)
    assert s.idle_gaps == [("PjitFunction(_stacked_epoch)", 100 / 1e9),
                           ("device_put", 100 / 1e9),
                           ("bench.window", 100 / 1e9)]
    assert s.top_ops[0] == ("other:fusion", 700 / 1e9)


def test_hlo_text_names():
    ev = tr.Event(DEV0, OPS, "%while.4 = (s32[]{:T(128)}, f32[4,6]{1,0:T(4,"
                  "128)}) while((s32[]{:T(128)}) %tuple.150), condition=%c",
                  "", 0.0, 10.0)
    assert tr.opcode(ev) == "while" and tr.instruction(ev) == "while"
    k = tr.Event(DEV0, OPS, '%_blocked_matmul.51 = f32[4,115200,8]{2,1,0:T(8,'
                 '128)} custom-call(f32[4,115200,25]{2,1,0:T(8,128)} %copy.2'
                 '80), custom_call_target="tpu_custom_call"', "", 0.0, 1.0)
    assert tr.layer_of(k) == "conv2d" and tr.kind(k) == "_blocked_matmul"
    c = tr.Event(DEV0, OPS, '%custom-call.43 = f32[4,1,2,128,128]{3,4,2,1,0:T'
                 '(8,128)S(1)} custom-call(%pad_maximum_fusion.2), custom_ca'
                 'll_target="InvertDiagBlocksLowerTriangular"', "", 0.0, 1.0)
    assert tr.layer_of(c) == "beta_solve"
    f = tr.Event(DEV0, OPS, "%fusion.3 = f32[5,5,1,6]{3,2,1,0:T(1,128)} fusio"
                 "n(u32[5]{0:T(128)S(1)} %_blocked_matmul.51)", "", 0.0, 1.0)
    assert tr.opcode(f) == "fusion" and tr.layer_of(f) is None


def test_device_ops_by_chip():
    ops = tr.device_ops(hand_events())
    assert sorted(ops) == [0, 1]
    assert len(ops[0]) == 5 and len(ops[1]) == 2


def test_recorded_chip_trace():
    """A traced window of tiny training jobs on one TPU v5e chip (4
    members x 5 batches of 10 images, 6c-12c): the reduction's numbers."""
    import os
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "testdata", "train_tiny.xplane.pb.gz")
    ev = tr.load(path)
    assert len(tr.device_ops(ev)[0]) == 853
    s = tr.summarize(ev, tr.window_of(ev, "bench.window"))
    assert s.window_s == 0.051822407
    assert s.busy_s == {0: 0.003754174}
    layers = s.layer_s[0]
    assert abs(layers["conv2d"] - 0.000872454) < 1e-12
    assert abs(layers["elm_stats"] - 4.5279e-05) < 1e-12
    assert abs(layers["beta_solve"] - 0.001316483) < 1e-12
    assert s.exposed_collective_s == {0: 0.0}
    assert s.top_ops[0][0] == "conv2d:_blocked_matmul"
    assert s.idle_gaps[0] == ("PjitFunction(maximum)", 0.0040206)
