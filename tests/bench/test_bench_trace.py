"""Trace reduction on a small trace recorded here on the CPU: the XLA
executor threads of the host stand in for a device's operation lines."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import jax
    import jax.numpy as jnp

    d = tmp_path_factory.mktemp("trace")
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(d))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("bench.eval"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.host"):
                np.linalg.svd(np.ones((200, 200)))
    jax.profiler.stop_trace()
    return trace.find_xplane(str(d))


def test_reduce_cpu_trace(recorded):
    s = trace.reduce_trace(recorded, device_plane="^/host:CPU$",
                           device_lines=("tf_XLA",))
    assert s.n_devices == 1
    assert 0 < s.busy_s < s.window_s
    assert 0 < s.idle_pct < 100
    assert s.span_count["bench.eval"] == 4
    assert s.span_count["bench.host"] == 4
    # device work happens inside the evaluator spans, not the host ones
    assert s.span_busy_s["bench.eval"] > 0
    assert s.span_busy_s["bench.eval"] <= s.span_s["bench.eval"] + 1e-9
    assert s.span_busy_s["bench.eval"] > 5 * s.span_busy_s["bench.host"]
    assert s.device_ops and all(v > 0 for _, v in s.device_ops)
    assert len(s.idle_gaps) <= 10
    names = {n for n, _ in s.idle_gaps}
    assert "bench.host" in names
    assert all(g > 0 for _, g in s.idle_gaps)


def test_no_device_plane_is_an_error(recorded):
    with pytest.raises(ValueError):
        trace.reduce_trace(recorded)


def test_interval_union_and_intersection():
    u = trace._union(np.array([[0, 2], [1, 3], [5, 6], [6, 7]], float))
    assert u.tolist() == [[0, 3], [5, 7]]
    assert trace._length(trace._clip(u, 1, 6)) == 3.0
    assert trace._intersect_len(u, np.array([[2, 5.5]])) == 1.5
