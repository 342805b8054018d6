"""The dense decoder's family (gated, tied, RMSNorm, GQA; ``bench/family.py``):
the weights of ``bench/weights.py``, the plain reference of
``bench/reference/qwen.py`` and the forward FLOPs of ``bench/work.py``.
A serving configuration without a ``family`` key is of this family."""
from bench import work
from bench.reference import qwen
from bench.weights import make_params  # noqa: F401

logits = qwen.logits
token_gaps = qwen.token_gaps
token_flops = work.lm_token_flops
span_flops = work.lm_span_flops


def kwargs(cfg: dict) -> dict:
    return qwen.kwargs(cfg["model"], cfg["reference"]["rms_norm_eps"])
