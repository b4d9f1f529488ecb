"""Helpers the per-layer metric readers share."""
from __future__ import annotations

import re

_SHAPE = re.compile(r"\b(?:f32|bf16|s32|u8|s8)\[(\d+(?:,\d+)*)\]")


def idle_share(ctx):
    t = ctx.get("trace") or {}
    if not t.get("window_s"):
        return None
    return 1.0 - t["busy_s"] / t["window_s"]


def custom_call_shapes(text: str):
    """(output shapes, operand shapes) of a custom call's HLO text, each a
    list of tuples; None for any other op."""
    if " custom-call(" not in text:
        return None
    outs, rest = text.split(" custom-call(", 1)
    ins = rest.split("), custom_call_target", 1)[0]
    parse = lambda t: [tuple(int(x) for x in m.group(1).split(","))  # noqa
                       for m in _SHAPE.finditer(t)]
    return parse(outs.split(" = ", 1)[-1]), parse(ins)


def roofline(ctx, program: str, cost_of):
    """Percent of the roofline over every call of ``program`` in the
    traced window: the sum over calls of max(ops / peak, bytes /
    bandwidth), over the summed device time of the calls' kernel op (the
    custom call inside the program; ``cost_of(outputs, operands)`` takes
    its shapes)."""
    t = ctx.get("trace") or {}
    peaks = ctx["peaks"]
    least = spent = 0.0
    for module, events in (t.get("module_events") or {}).items():
        if program not in module:
            continue
        for st in events:
            shapes = custom_call_shapes(str(st.get("hlo_text", "")))
            if shapes is None:
                continue
            ops, nbytes = cost_of(*shapes)
            least += max(ops / peaks["bf16_flops"],
                         nbytes / peaks["hbm_bytes_per_s"])
            spent += float(st.get("__dur_s__", 0.0))
    if spent <= 0:
        return None
    return 100.0 * least / spent
