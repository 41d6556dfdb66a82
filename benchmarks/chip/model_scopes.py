"""Device time per step under the model's own scopes inside the
API-BCD step: model.attn (attention, its input norm included),
model.mlp (a dense layer's MLP) and the expert layer's model.moe.router,
.dispatch, .experts and .shared (`repro.models.transformer` and
`repro.models.moe` name them with `jax.named_scope`).

Forward, backward and recomputed work alike count under the scope they
were traced in: an operation's op_name carries its scopes, and the last
model scope in it names it.  The reduction is `phases.reduce`'s (each
device operation's self time, attributed to the step by time
containment), over op_names that keep only that scope.  A program that
names none of these scopes reads None.
"""
from __future__ import annotations

import re
import sys
import traceback

import phases

_SCOPE = re.compile(
    r"model\.(attn|mlp|moe\.(?:router|dispatch|experts|shared))"
    r"(?![A-Za-z_.])")


def model_scope(op_name):
    """"attn", "mlp", "moe_router", ... of an op_name; None outside."""
    found = _SCOPE.findall(op_name or "")
    return found[-1].replace(".", "_") if found else None


# what `phases.scope` reads an op's direction from
_WAY = {"recompute": "rematted_computation/", "backward": "transpose(",
        "forward": "jvp(", "": ""}


def reduce(trace, module, names):
    """`phases.Phases` whose model scope is the op's own model scope
    (its phase and direction kept)."""
    renamed = {}
    for instr, op_name in names.items():
        sc = model_scope(op_name)
        if sc is not None:
            phase, _, way = phases.scope(op_name)
            renamed[instr] = (f"apibcd.{phase}/" if phase else "") \
                + f"{_WAY[way]}model.{sc}"
    return phases.reduce(trace, module, renamed)


def table(found):
    """ms per step of each model scope and direction, device by device,
    for whoever reads the run's log (no metric reads it)."""
    lines = []
    for dev, n in found.steps.items():
        if not n:
            continue
        lines.append(f"model scopes {dev}: {n} steps")
        for (_, sc, way), sec in sorted(found.detail[dev].items(),
                                        key=lambda kv: -kv[1]):
            if sc:
                lines.append(f"  model.{sc:<14} {way or '-':<10} "
                             f"{1e3 * sec / n:10.4f}")
    return "\n".join(lines)


def of(cell, trace):
    """The reduction of a traced run of a training cell, computed once
    per trace, its table to stderr; None where the step names no model
    scope or the reduction cannot be made (the reason goes to stderr)."""
    if hasattr(trace, "_model_scopes"):
        return trace._model_scopes
    found = None
    try:
        module, names = phases.op_names(phases.step_hlo(cell))
        found = reduce(trace, module, names)
        if not any(d for d in found.detail.values()):
            found = None
        else:
            print(table(found), file=sys.stderr, flush=True)
    except Exception:           # noqa: BLE001 -- optional instrumentation
        print("model_scopes: no reduction\n" + traceback.format_exc(),
              file=sys.stderr, flush=True)
    trace._model_scopes = found
    return found


def per_step_ms(found, scopes):
    """Device ms per step under any of `scopes`, the mean over the
    devices that ran the step; None where nothing ran there."""
    if found is None:
        return None
    vals = []
    for dev, n in found.steps.items():
        if n:
            sec = sum(s for (_, sc, _), s in found.detail[dev].items()
                      if sc in scopes)
            vals.append(1e3 * sec / n)
    if not vals or not any(vals):
        return None
    return sum(vals) / len(vals)


def read(cell, trace, scopes):
    return per_step_ms(of(cell, trace), scopes)
