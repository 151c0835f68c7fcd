"""The comparison that decides `correct`.

Every answer of the window is compared with the plain reference of its
statement and literals: strings and integers exactly, decimals by relative
error. An OK packet is an answer too: where the reference is an `int` (the
affected-row count; 0 for BEGIN and COMMIT) the answer has to be that `int`,
and an `int` where rows were due, or rows where an `int` was due, is wrong.
Three numbers come out, each with a limit of its own:

  missing_answers   statements that failed or never answered      limit 0
  wrong_answers     answers with another shape, key or text       limit 0
  rel_err_max       widest relative gap of a numeric cell         limits.json
"""

from __future__ import annotations

from decimal import Decimal, InvalidOperation

TINY = Decimal("1e-12")


def compare_rows(got, ref):
    """(wrong: bool, widest relative error) of one answer."""
    if isinstance(got, int) or isinstance(ref, int):
        return type(got) is not type(ref) or got != ref, 0.0
    if isinstance(got, str) or len(got) != len(ref):
        return True, 0.0
    worst = 0.0
    for g_row, r_row in zip(got, ref):
        if len(g_row) != len(r_row):
            return True, worst
        for g, r in zip(g_row, r_row):
            if isinstance(r, str):
                if g != r:
                    return True, worst
            elif isinstance(r, int):
                try:
                    if int(g) != r:
                        return True, worst
                except (TypeError, ValueError):
                    return True, worst
            else:
                try:
                    gap = abs(Decimal(g) - r) / max(abs(r), TINY)
                except (TypeError, InvalidOperation):
                    return True, worst
                worst = max(worst, float(gap))
    return False, worst


def judge(records, reference_of, limit: float) -> dict:
    """`records` as the load generator returns them; `reference_of(kind,
    literals)` gives the rows due. Returns the numbers and `correct`."""
    missing = wrong = 0
    worst = 0.0
    first_bad = None
    for kind, lit, _t0, _t1, rows, _who in records:
        if isinstance(rows, str):
            missing += 1
            first_bad = first_bad or f"{kind} {lit}: {rows[:200]}"
            continue
        bad, err = compare_rows(rows, reference_of(kind, lit))
        if bad:
            wrong += 1
            first_bad = first_bad or f"{kind} {lit}: got {str(rows)[:300]}"
        worst = max(worst, err)
    numbers = {"missing_answers": {"value": missing, "limit": 0},
               "wrong_answers": {"value": wrong, "limit": 0},
               "rel_err_max": {"value": worst, "limit": limit}}
    correct = (missing == 0 and wrong == 0 and worst <= limit
               and len(records) > 0)
    return {"correct": correct, "compared": numbers, "first_bad": first_bad}
