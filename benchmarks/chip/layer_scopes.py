"""Device time by the model's layer scopes, read from a trace summary.

``trace_reduce.summarize`` sums time by scope for its ``SCOPES`` alone (the
runtime's ``fwd_bwd`` and ``optimizer``).  It also keeps every op's time in
``Summary.top_ops``, under the op's label ``name [path]``, where ``path`` is
the tail of the op's ``op_name`` (``trace_reduce.Op.label``: its leading
``jit(...)/`` components dropped, then its last ``TAIL`` characters).

The model opens each layer scope close to the work it names, so the scope
is a component of that tail: ``.../checkpoint/rematted_computation/ssd/...``
(``tests/test_model_scopes.py`` holds every op of the compiled step to
that).  No layer scope is opened inside another, so an op counts toward
one layer at most; all of them lie inside the runtime's ``model`` scope,
inside ``fwd_bwd``.  A program that names no layer scope reads nothing.
"""
from __future__ import annotations

import re

#: The program's layer scopes (``repro.models.common.LAYER_SCOPES``).
LAYERS = ("embed", "norm", "ssm_proj", "ssm_conv", "ssd", "attention", "mlp",
          "head")
#: Characters of an op's path that its label keeps.
TAIL = 80

_LABEL = re.compile(r"^\S+ \[(.*)\]$")


def label_layer(label: str) -> str | None:
    """The layer scope in an op label's path, innermost first; ``None`` where
    it names none.  A path cut to ``TAIL`` characters may begin inside a
    component, so its first component is not read."""
    m = _LABEL.match(label)
    if not m:
        return None
    parts = m.group(1).split("/")
    if len(m.group(1)) >= TAIL:
        parts = parts[1:]
    return next((p for p in reversed(parts) if p in LAYERS), None)


def layer_s(summary) -> dict[str, float]:
    """{layer: device seconds in the window, mean over devices}, for the
    layers some op's label names."""
    out: dict[str, float] = {}
    for label, seconds in summary.top_ops:
        layer = label_layer(label)
        if layer is not None:
            out[layer] = out.get(layer, 0.0) + seconds
    return out


def layer_ms(ctx, layer: str) -> float | None:
    """Device ms a step under ``layer``; ``None`` in an untraced run or
    where no op is under it."""
    t = ctx.trace
    if t is None or not t.steps:
        return None
    s = layer_s(t).get(layer)
    return 1e3 * s / t.steps if s else None
