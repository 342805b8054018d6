"""A served configuration's family: its weights, its plain reference and
its work count.

A serving configuration file may name its family (``"family": "<module>"``,
``dense`` where it names none); the family is ``bench/reference/<module>.py``,
found by name beside the configurations, traffic mixes and metric readers,
and it provides:

- ``make_params(model, seed)``: the weights, made from the seed on the
  device in one jitted call, in the layout the program serves;
- ``kwargs(cfg)``: the static keyword arguments of ``logits`` for the
  configuration (its whole file);
- ``logits(params, tokens, **kwargs, dtype=...)``: float32 logits
  ``[S, vocab]`` of the plain reference's causal forward pass over
  ``tokens`` [S], weights and activations cast to ``dtype`` (bfloat16 is
  the control), products at ``highest``;
- ``token_gaps(ref_logits, chosen)``: how far each chosen token's logit
  lies below the best, ``[S]``;
- ``token_flops(model, ctx)`` and ``span_flops(model, start, n)``: forward
  FLOPs of one token attending ``ctx`` positions, and of ``n`` consecutive
  tokens at positions ``start..``.
"""
from __future__ import annotations

import importlib.util

DEFAULT = "dense"
FIELDS = ("make_params", "kwargs", "logits", "token_gaps", "token_flops",
          "span_flops")

_LOADED: dict = {}


def load(cell):
    """The family module of the cell's configuration (``harness.Cell``)."""
    name = cell.config.get("family", DEFAULT)
    path = cell.bench_dir / "reference" / f"{name}.py"
    if path not in _LOADED:
        from bench.harness import Refused

        spec = importlib.util.spec_from_file_location(
            f"bench_family_{name.replace('.', '_').replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        missing = [f for f in FIELDS if not callable(getattr(mod, f, None))]
        if missing:
            raise Refused(f"family {path.name} lacks {missing}")
        _LOADED[path] = mod
    return _LOADED[path]
