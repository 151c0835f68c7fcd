"""Device time in collectives, per statement: the exchange layer's own time.

Over the traced sub-windows, the seconds of device ops whose HLO category
(`trace.category`, as `reduce_events` keys `per_kind[kind]["ops"]`) starts
with a collective's name, so in its plain, `-start` or `-done` form, which
`reduce_events` has already averaged over the chips, / statements sent there
* 1000.

None without a trace. 0.0, not None, where a trace holds no such op: a run
whose statements fell back to one chip then reads 0 and is seen.
"""

from __future__ import annotations

# An op is named for its HLO opcode (`all-gather.5`) or, where the lowering
# kept it, for the JAX primitive (`all_to_all.3`, seen on the v5e): both forms.
_OPCODES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
            "collective-permute")
COLLECTIVES = _OPCODES + tuple(c.replace("-", "_") for c in _OPCODES)


def read(ctx: dict):
    red = ctx.get("trace")
    statements = sum((ctx.get("traced_statements") or {}).values())
    if red is None or not statements:
        return None
    seconds = sum(s for k in red["per_kind"].values()
                  for cat, s in k["ops"].items()
                  if cat.startswith(COLLECTIVES))
    return seconds / statements * 1000.0
