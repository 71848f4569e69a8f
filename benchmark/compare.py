"""The comparison that decides ``correct``: the Arrow tables the timed
requests returned against the plain reference of queries/<query>.py.

Everything is exact. ``wrong`` is 1 where the columns, the row count, any
exact column (keys, dates, strings, counts) or any DECIMAL value differ.
``units_off`` is the widest gap of a DECIMAL value from the reference, in
units of that column's last place: 0 for a sound answer, and what says how
far off a wrong one was.

A query file declares the shape of its answer: ``EXACT_COLUMNS`` (names, in
the answer's order), ``DECIMAL_COLUMNS`` ({name: scale}) and, where an exact
column is not an int64 or a string, ``EXACT_TYPES`` ({name: the Arrow type's
name, as ``date32``}). Either of the first two may be empty: an answer of
strings and counts alone (Q12) is decided by its exact columns, and its
``units_off`` is 0.
"""

import pyarrow as pa


def _exact_values(col: pa.ChunkedArray) -> list:
    if pa.types.is_date32(col.type):
        col = col.cast(pa.int32())
    return col.to_pylist()


def _unscaled(col: pa.ChunkedArray, scale: int):
    """Decimal (or, from a float engine, double) column as integers in
    units of 10**-scale; None where a value is null or not finite."""
    out = []
    for v in col.to_pylist():
        if v is None or v != v or v in (float("inf"), float("-inf")):
            return None
        if isinstance(v, float):
            out.append(round(v * 10 ** scale))
        else:
            out.append(int(v.scaleb(scale).to_integral_value()))
    return out


def answer_readings(got: pa.Table, want: dict, query) -> dict:
    """``{"wrong": 0|1, "units_off": int, "why": str}`` for one answer."""
    declared = tuple(query.EXACT_COLUMNS) + tuple(query.DECIMAL_COLUMNS)
    if set(got.schema.names) != set(declared):
        return {"wrong": 1, "units_off": 0,
                "why": f"columns {got.schema.names}"}
    n = len(want[declared[0]])  # every column of a reference is as long
    if got.num_rows != n:
        return {"wrong": 1, "units_off": 0,
                "why": f"{got.num_rows} rows, want {n}"}
    for c in query.EXACT_COLUMNS:
        if _exact_values(got[c]) != list(want[c]):
            return {"wrong": 1, "units_off": 0, "why": f"column {c} differs"}
    worst, why = 0, ""
    for c, scale in query.DECIMAL_COLUMNS.items():
        g = _unscaled(got[c], scale)
        if g is None:
            return {"wrong": 1, "units_off": 0,
                    "why": f"null or non-finite in {c}"}
        off = max((abs(a - b) for a, b in zip(g, want[c])), default=0)
        if off > worst:
            worst, why = off, f"{c} off by {off} units of 1e-{scale}"
    return {"wrong": int(worst > 0), "units_off": worst, "why": why}


def control_table(want: dict, query) -> pa.Table:
    """A reference answer (computed in float64 money, say) as the Arrow
    table the program would have returned: the control in its place."""
    from decimal import Decimal
    types = getattr(query, "EXACT_TYPES", {})
    cols = {}
    for c in query.EXACT_COLUMNS:
        kind = types.get(c)
        if kind == "date32":  # the references hold dates as days since 1970
            cols[c] = pa.array(list(want[c]), pa.int32()).cast(pa.date32())
        else:
            cols[c] = pa.array(list(want[c]),
                               getattr(pa, kind)() if kind else None)
    for c, scale in query.DECIMAL_COLUMNS.items():
        cols[c] = pa.array([Decimal(v).scaleb(-scale) for v in want[c]],
                           pa.decimal128(38, scale))
    return pa.table(cols)


def judge(answers: list, references: dict, queries: dict) -> dict:
    """All answers of a window. ``answers`` is a list of (reference key,
    table); ``queries[key]`` is the query module. Identical tables (the
    usual case: one text, one data set) are compared once and counted
    every time."""
    seen, wrong, worst, whys = {}, 0, 0, []
    for key, table in answers:
        ident = (key, table.schema.to_string(),
                 tuple(tuple(c.to_pylist()) for c in table.columns))
        r = seen.get(ident)
        if r is None:
            r = seen[ident] = answer_readings(table, references[key],
                                              queries[key])
        wrong += r["wrong"]
        worst = max(worst, r["units_off"])
        if r["why"] and f"{key}: {r['why']}" not in whys:
            whys.append(f"{key}: {r['why']}")
    return {"answers_wrong": wrong, "decimal_units_off_max": worst,
            "distinct_answers": len(seen), "whys": whys[:5]}
