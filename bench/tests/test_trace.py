"""The trace reduction: busy union, per-program time, gap attribution."""
from devtrace import reduce

DEV, HOST = "/device:TPU:0", "/host:CPU"


def _ev(plane, line, name, s, e):
    return (plane, line, name, float(s), float(e))


def test_reduce_busy_programs_and_gaps():
    evs = [
        _ev(HOST, "python", "bench.window", 0, 100),
        _ev(HOST, "python", "bench.step", 5, 95),
        _ev(HOST, "python", "bench.wait", 32, 48),
        _ev(HOST, "python", "bench.tick", 60, 90),
        _ev(DEV, "XLA Ops", "fusion.1", 10, 20),
        _ev(DEV, "XLA Ops", "copy.2", 15, 30),          # overlaps fusion.1
        _ev(DEV, "XLA Ops", "fusion.1", 50, 60),
        _ev(DEV, "XLA Ops", "fusion.9", 120, 130),      # after the window
        _ev(DEV, "XLA Modules", "jit__decode_impl(17)", 10, 30),
        _ev(DEV, "XLA Modules", "jit__fused_decode_impl(3)", 10, 30),
        _ev(DEV, "XLA Modules", "jit__prefill_chunk_ssm_impl(4)", 50, 60),
    ]
    r = reduce(evs)
    assert r["window_s"] == 100e-9
    assert abs(r["busy_s"] - 30e-9) < 1e-18          # [10,30] and [50,60]
    assert r["programs"]["decode"] == {"runs": 1, "seconds": 20e-9}
    assert r["programs"]["prefill"] == {"runs": 1, "seconds": 10e-9}
    ops = dict((n, s) for n, s in r["device_ops"])
    assert abs(ops["fusion.1"] - 20e-9) < 1e-18 and "fusion.9" not in ops
    gaps = dict((n, s) for n, s in r["idle_gaps"])
    # [0,10] under the step, [30,50] in the wait, [60,100] in the tick
    assert gaps.keys() == {"bench.step", "bench.wait", "bench.tick"}
    assert abs(gaps["bench.tick"] - 40e-9) < 1e-18
    assert abs(gaps["bench.wait"] - 20e-9) < 1e-18
    assert abs(gaps["bench.step"] - 10e-9) < 1e-18


def test_reduce_without_device_or_window_reads_nothing():
    assert reduce([_ev(HOST, "python", "bench.window", 0, 10)]) is None
    assert reduce([_ev(DEV, "XLA Ops", "f", 0, 10)]) is None


def test_events_from_a_recorded_cpu_profile(tmp_path):
    """A real profile, recorded here: the harness's host spans are found
    (a CPU profile has no device plane, so it reduces to nothing)."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from devtrace import events
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        with TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    evs = events(str(tmp_path))
    names = [e[2] for e in evs]
    assert "bench.window" in names and "bench.step" in names
    (ws, we), = [(e[3], e[4]) for e in evs if e[2] == "bench.window"]
    (ss, se), = [(e[3], e[4]) for e in evs if e[2] == "bench.step"]
    assert ws <= ss <= se <= we
    assert reduce(evs) is None


def test_op_names_keep_op_type_and_instruction():
    from devtrace import op_name
    assert op_name("%copy.625 = bf16[270336,16,96]{2,1,0:T(8,128)(2,1)} "
                   "copy(bf16[270336,16,96]{0,2,1:T(8,128)(2,1)} %pool_v.1)"
                   ) == "copy bf16[270336,16,96] (copy.625)"
    assert op_name("%slice_bitcast_fusion.188 = (bf16[3072,3072]{0,1:T(8,128)"
                   "(2,1)}, bf16[3072,3072]{0,1:T(8,128)(2,1)}) fusion(bf16["
                   "32,3072,3072]{2,1,0} %bitcast.46), kind=kLoop"
                   ) == "fusion bf16[3072,3072] (slice_bitcast_fusion.188)"
    assert op_name("fusion.1") == "fusion.1"
