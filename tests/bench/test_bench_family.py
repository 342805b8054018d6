"""A served configuration's family (``bench/family.py``): without the
``family`` key the serve driver takes the dense decoder's weights,
reference and FLOP count unchanged; a family of another architecture is
added as files alone (a configuration naming it and
``bench/reference/<family>.py``) and runs a CPU serve rehearsal, correct,
with its own weights, reference and work count."""
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import family, harness, serve, weights, work  # noqa: E402
from bench.reference import qwen  # noqa: E402

import test_bench_serve_run as serve_run  # noqa: E402

# a dense decoder with an untied output head and no QKV bias: weights the
# dense family refuses to make, and a head its reference does not have
TOY = '''"""Toy family: dense decoder, untied head, no QKV bias."""
from collections import Counter
from functools import partial

import jax
import jax.numpy as jnp

from bench import weights, work
from bench.reference import qwen

CALLS = Counter()


@partial(jax.jit, static_argnames=("vocab", "d", "layers", "h", "kvh",
                                   "hd", "d_ff"))
def _init(key, *, vocab, d, layers, h, kvh, hd, d_ff):
    k1, k2 = jax.random.split(key)
    p = weights._init(k1, vocab=vocab, d=d, layers=layers, h=h, kvh=kvh,
                      hd=hd, d_ff=d_ff, bias=False, dtype=jnp.float32)
    p["lm_head"] = {"w": jax.random.normal(k2, (d, vocab)) * d ** -0.5}
    return p


def make_params(model, seed):
    CALLS["make_params"] += 1
    return _init(jax.random.key(seed), vocab=model["vocab"],
                 d=model["d_model"], layers=model["n_layers"],
                 h=model["n_heads"], kvh=model["n_kv_heads"],
                 hd=model["head_dim"], d_ff=model["d_ff"])


def kwargs(cfg):
    m = cfg["model"]
    return dict(n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
                head_dim=m["head_dim"], theta=float(m["rope_theta"]),
                eps=float(cfg["reference"]["rms_norm_eps"]))


@partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "head_dim",
                                   "theta", "eps", "dtype"))
def _logits(params, tokens, *, n_heads, n_kv_heads, head_dim, theta, eps,
            dtype):
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    mm = partial(jnp.matmul, precision="highest")
    s = tokens.shape[0]
    pos = jnp.arange(s)
    x = p["embed"]["e"][tokens]
    causal = pos[None, :] <= pos[:, None]
    rep = n_heads // n_kv_heads
    for blk in p["blocks"]:
        a = blk["attn"]
        h = qwen._rms(x, blk["norm1"]["g"], eps)
        q = mm(h, a["wq"]["w"]).reshape(s, n_heads, head_dim)
        k = mm(h, a["wk"]["w"]).reshape(s, n_kv_heads, head_dim)
        v = mm(h, a["wv"]["w"]).reshape(s, n_kv_heads, head_dim)
        q, k = qwen._rope(q, pos, theta), qwen._rope(k, pos, theta)
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision="highest")
        sc = sc.astype(jnp.float32) / jnp.sqrt(jnp.float32(head_dim))
        w = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), -1)
        o = jnp.einsum("hqk,khd->qhd", w.astype(dtype), v,
                       precision="highest").reshape(s, -1)
        x = x + mm(o, a["wo"]["w"])
        f = blk["ffn"]
        g, u = jnp.split(mm(qwen._rms(x, blk["norm2"]["g"], eps),
                            f["wi"]["w"]), 2, axis=-1)
        x = x + mm(jax.nn.silu(g) * u, f["wo"]["w"])
    x = qwen._rms(x, p["final_norm"]["g"], eps)
    return mm(x, p["lm_head"]["w"]).astype(jnp.float32)


def logits(params, tokens, *, dtype=jnp.float32, **kw):
    CALLS["logits"] += 1
    return _logits(params, tokens, dtype=dtype, **kw)


token_gaps = qwen.token_gaps


def token_flops(model, ctx):
    CALLS["token_flops"] += 1
    return work.lm_token_flops(model, ctx)


def span_flops(model, start, n):
    CALLS["span_flops"] += 1
    return work.lm_span_flops(model, start, n)
'''


def test_dense_family_is_the_default_arithmetic():
    cell = serve_run.tiny_cell()
    assert "family" not in cell.config
    fam = family.load(cell)
    assert Path(fam.__file__).name == "dense.py"
    assert fam.make_params is weights.make_params
    assert fam.logits is qwen.logits and fam.token_gaps is qwen.token_gaps
    m = cell.config["model"]
    for ctx in (1, 17, 96):
        assert fam.token_flops(m, ctx) == work.lm_token_flops(m, ctx)
    for start, n in ((0, 16), (40, 7)):
        assert fam.span_flops(m, start, n) == work.lm_span_flops(m, start, n)
    assert fam.kwargs(cell.config) == qwen.kwargs(
        m, cell.config["reference"]["rms_norm_eps"])


def test_dense_family_reference_gaps_are_qwens():
    import jax.numpy as jnp

    cell = serve_run.tiny_cell()
    cfg, m = cell.config, cell.config["model"]
    fam = family.load(cell)
    params = fam.make_params(m, 11)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, m["vocab"], 20).tolist()
    generated = rng.integers(0, m["vocab"], 9).tolist()
    gap, control, n = serve.reference_gaps(fam, cfg, params,
                                           [(prompt, generated)], True)
    # the same comparison written out with the qwen reference itself
    seq = prompt + generated[:-1]
    toks = np.zeros(cfg["service"]["max_len"], np.int32)
    toks[:len(seq)] = seq
    kw = qwen.kwargs(m, cfg["reference"]["rms_norm_eps"])
    ref = qwen.logits(params, jnp.asarray(toks), **kw)
    pick = np.zeros_like(toks)
    lo = len(prompt) - 1
    pick[lo:lo + len(generated)] = generated
    want = float(np.asarray(qwen.token_gaps(ref, jnp.asarray(pick)))[
        lo:lo + len(generated)].max())
    assert n == len(generated) and gap == want and gap > 0
    low = qwen.logits(params, jnp.asarray(toks), **kw, dtype=jnp.bfloat16)
    top = np.asarray(jnp.argmax(low, axis=1), np.int32)
    assert control == float(np.asarray(qwen.token_gaps(
        ref, jnp.asarray(top)))[lo:lo + len(generated)].max())


def test_backlog_flops_are_unchanged(monkeypatch, tmp_path):
    """The serving record's FLOPs are the dense count of PR 12: every
    prefill chunk of the window by ``lm_span_flops`` and every decoded
    token by ``lm_token_flops`` over the positions it attends."""
    _cpu(monkeypatch, tmp_path)
    cell = serve_run.tiny_cell()
    m = cell.config["model"]
    cell.traffic.update(warmup_s=0.0)    # decode steps in the window
    out = serve.run(serve_run.ctx(cell, seconds=3.0))
    rec = out.record
    assert rec["decodes"]
    per_position = 4 * m["n_layers"] * m["n_heads"] * m["head_dim"]
    for _, _, n, flops, positions in rec["decodes"]:
        assert flops == n * work.lm_token_flops(m, 0) \
            + per_position * positions
    t_end = out.t_open + rec["window_s"]
    prefills = [p for p in rec["prefills"] if out.t_open <= p[1] < t_end]
    assert rec["flops"] == sum(work.lm_span_flops(m, p[3], p[2])
                               for p in prefills) \
        + sum(d[3] for d in rec["decodes"])


def _toy_tree(tmp_path):
    """A copy of the benchmark's files with a toy family added as files
    and entries only; returns (BENCHMARK dict, bench dir, cell name)."""
    bench_dir = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    base = json.loads((bench_dir / "configs" / "qwen1.5-0.5b.json")
                      .read_text())
    toy = dict(base, name="toy-untied", family="toy_untied")
    toy["model"] = dict(base["model"], vocab=512, d_model=64, n_layers=2,
                        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                        max_seq=256, qkv_bias=False, tie_embeddings=False)
    toy["service"] = dict(base["service"], max_batch=4, max_len=96)
    (bench_dir / "configs" / "toy-untied.json").write_text(json.dumps(toy))
    mix = json.loads((bench_dir / "traffic" / "sharegpt-backlog.json")
                     .read_text())
    mix.update(max_len=96, max_prompt=40, chunk=16, warmup_s=0.0,
               n_requests=16, trace_seconds=1.0, check_requests=3)
    (bench_dir / "traffic" / "toy-backlog.json").write_text(json.dumps(mix))
    (bench_dir / "reference" / "toy_untied.py").write_text(TOY)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy-untied", "source": "test",
                             "file": "bench/configs/toy-untied.json",
                             "reduced": [], "why": "test"})
    name = "serve.toy-untied.toy-backlog"
    bench["workloads"].append({"name": name, "config": "toy-untied",
                               "traffic": "toy-backlog", "chips": 1,
                               "why": "test"})
    bench["end_to_end"] = [
        dict(e, workloads=e["workloads"] + [name])
        if "output_tokens_per_s" == e["name"] else e
        for e in bench["end_to_end"]]
    return bench, bench_dir, name


def _cpu(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path / "bench_out")
    monkeypatch.setattr(work, "peaks", lambda kind: {
        "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def test_a_family_is_added_as_files_only(tmp_path, monkeypatch):
    import jax

    _cpu(monkeypatch, tmp_path)
    bench, bench_dir, name = _toy_tree(tmp_path)
    cell = harness.resolve_cell(bench, name, bench_dir)
    assert cell.traffic["driver"] == "serve"
    fam = family.load(cell)
    assert Path(fam.__file__).name == "toy_untied.py"
    calls = fam.token_flops.__globals__["CALLS"]
    with pytest.raises(ValueError):       # the dense family refuses it
        weights.make_params(cell.config["model"], 1)
    line = harness.run_cell(serve_run.ctx(cell, seconds=3.0), jax.devices())
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert line["metrics"]["output_tokens_per_s"]["value"] > 0
    # its own weights, reference and work count served the run
    assert calls["make_params"] == 1 and calls["logits"] >= 1
    assert calls["token_flops"] > 0
    params = fam.make_params(cell.config["model"], 5)
    assert "lm_head" in params


def test_a_family_reads_the_control_apart(tmp_path, monkeypatch):
    _cpu(monkeypatch, tmp_path)
    bench, bench_dir, name = _toy_tree(tmp_path)
    cell = harness.resolve_cell(bench, name, bench_dir)
    out = serve.run(serve_run.ctx(cell, seconds=2.0), control=True)
    (_, value, limit), = out.checks
    assert value <= limit < out.record["control"]


def test_a_family_without_its_file_is_refused(tmp_path):
    bench, bench_dir, name = _toy_tree(tmp_path)
    (bench_dir / "reference" / "toy_untied.py").unlink()
    with pytest.raises(harness.Refused):
        harness.resolve_cell(bench, name, bench_dir)
