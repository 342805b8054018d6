"""Device time per program and operation inside the window, on a small
trace recorded here on the CPU (the XLA executor threads of the host stand
in for a device's operation lines): operations split so that no instant
counts twice add up to the window's busy time, an operation run before the
window opens is not counted, idle gaps are named by the innermost of the
harness's and the program's spans, and a compiled program's HLO names each
instruction's scope."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

CPU = dict(device_plane="^/host:CPU$", device_lines=("tf_XLA",))


def early(x):
    import jax.numpy as jnp

    return jnp.sin(x @ x.T) @ x


def inner(x):
    import jax.numpy as jnp

    return jnp.tanh(x @ x) * 2.0


def outer(x):
    import jax

    return jax.jit(inner)(x + 1.0) @ x


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import jax
    import jax.numpy as jnp

    d = tmp_path_factory.mktemp("trace")
    before = jax.jit(early)
    f = jax.jit(outer)
    x = jnp.ones((384, 384))
    before(x).block_until_ready()
    f(x).block_until_ready()
    jax.profiler.start_trace(str(d))
    for _ in range(3):                 # before the window: not counted
        before(x).block_until_ready()
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("bench.eval"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.host"):
                with jax.profiler.TraceAnnotation("repro.ga.step"):
                    np.linalg.svd(np.ones((200, 200)))
    jax.profiler.stop_trace()
    hlo = f.lower(x).compile().as_text()
    return trace.find_xplane(str(d)), hlo


def test_module_ops_add_up_to_the_busy_time(recorded):
    s = trace.reduce_trace(recorded[0], **CPU)
    total = sum(v for ops in s.module_ops.values() for v in ops.values())
    assert 0 < total <= s.busy_s * (1 + 1e-9)
    assert total == pytest.approx(s.busy_s, rel=1e-9)
    # the jitted function of the window is its own program
    mod = [m for m in s.module_ops if m.startswith("jit_")]
    assert mod and all(v >= 0 for m in mod for v in s.module_ops[m].values())


def test_an_op_before_the_window_is_not_counted(recorded):
    s = trace.reduce_trace(recorded[0], **CPU)
    assert not any("early" in m for m in s.module_ops)
    assert not any("early" in n for n, _ in s.device_ops)


def test_device_ops_name_program_and_op(recorded):
    s = trace.reduce_trace(recorded[0], **CPU)
    assert 0 < len(s.device_ops) <= 10
    flat = {f"{m}:{op}": v for m, ops in s.module_ops.items()
            for op, v in ops.items()}
    for name, v in s.device_ops:
        assert flat[name] == v
    values = [v for _, v in s.device_ops]
    assert values == sorted(values, reverse=True)
    assert values[-1] >= max(sorted(flat.values())[:-len(values)] or [0])


def test_idle_gaps_named_by_program_spans(recorded):
    s = trace.reduce_trace(recorded[0], **CPU)
    names = {n for n, _ in s.idle_gaps}
    assert names <= {"bench.eval", "bench.host", "repro.ga.step", "host"}
    # the host's SVD runs inside repro.ga.step, the innermost of the two
    assert "repro.ga.step" in names and "bench.host" not in names
    # the program's spans name gaps and nothing else
    assert set(s.span_s) == {"bench.eval", "bench.host"}


def test_self_times_split_nested_and_overlapping_ops():
    ops = [(0, 10, "outer"), (2, 4, "a"), (6, 9, "b"), (8, 12, "c"),
           (20, 30, "late")]
    got = trace._self_times(ops, 1, 25)
    # outer 1-2, 4-6; a 2-4; b 6-8; c 8-12 (it started last); late 20-25
    assert got == {"outer": 3, "a": 2, "b": 2, "c": 4, "late": 5}
    assert sum(got.values()) == trace._length(trace._clip(trace._union(
        np.asarray([o[:2] for o in ops], float)), 1, 25))


def test_hlo_op_names(recorded):
    module, names = trace.hlo_op_names(recorded[1])
    assert module == "jit_outer"
    scoped = {op for op, path in names.items()
              if "jit(inner)" in path.split("/")}
    assert scoped and scoped != set(names)
    assert all(path.startswith("jit(outer)/") for path in names.values()
               if "/" in path)


def test_module_names_drop_the_program_id():
    assert trace._module_name("jit_decode_bs16(42)") == "jit_decode_bs16"
    assert trace._module_name("jit_f") == "jit_f"
