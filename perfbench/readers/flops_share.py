"""Reader ``flops_share``: the share, in percent, of the chip's bf16 peak
that the step's model arithmetic reaches: the configuration's
``flops.per_worker_step`` (what ANY implementation of the step must
compute, counted from the shapes) over the device time of the leaf ops
under the named scopes per traced step, over ``peaks.json``'s
``bf16_flops_per_s``.

Parameters: ``scopes`` (the scopes whose device time is the denominator).
Reads ``ctx["ops"]``, ``ctx["config"]["flops"]`` and ``ctx["peaks"]``; a
configuration without a ``flops`` group, a trace without steps or without
an op under the scopes leaves nothing to read and the reader returns
``None``. The operations are counted once however many bf16 passes the
stated precision takes, so at ``HIGHEST`` (six passes) the share cannot
pass a sixth of 100.
"""

from __future__ import annotations

from perfbench.lib import trace_reduce as tr


def read(ctx, p):
    spec = (ctx.get("config") or {}).get("flops")
    ops = ctx.get("ops")
    if not spec or not ops:
        return None
    steps = tr.steps_traced(ops)
    t = tr.time_where(ops, lambda o: tr.in_scope(o, p["scopes"]))
    if not steps or t <= 0:
        return None
    achieved = float(spec["per_worker_step"]) / (t / steps)
    return 100.0 * achieved / float(ctx["peaks"]["bf16_flops_per_s"])
