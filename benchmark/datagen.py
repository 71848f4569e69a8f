"""The benchmark's own TPC-H data, made from ``--seed`` with numpy.

The schema, the types and the distributions are the specification's
(cl. 1.4 and 4.2.3, as dbgen populates them): all 16/9/8 columns of
``lineitem``/``orders``/``customer``; money as DECIMAL(15,2) (held here as
whole cents, handed to the program as Arrow ``decimal128(15, 2)``); sparse
order keys (the first 8 of every 32); 1 to 7 lines per order, every line
joining its order; ship date = order date + 1..121 days, commit date + 30..90,
receipt date = ship date + 1..30; return flag and line status from the
current date 1995-06-17; extended price = quantity x the part's retail
price; customers whose key divides by 3 place no order; order status and
total price derived from the order's lines. What departs from dbgen is
listed in each configuration's ``assumed``: numpy's generator instead of
dbgen's, the number of lines per order a seeded permutation of equal shares
of 1..7 (so the row count follows the scale and never the seed), and
comment text cut from a seeded pool of words instead of dbgen's grammar.

``make(tables, sf, seed)`` gives {table: {column: values}} with numbers as
numpy arrays, money as int64 cents, fixed-vocabulary strings as int8 codes
into ``labels(column)``, dates as int32 days since 1970 and free text as
``Text``; ``arrow(columns)`` wraps them as the NOT NULL Arrow table the
front-end registers. It imports numpy and pyarrow only.
"""

import datetime

import numpy as np
import pyarrow as pa


def date_i(y, m, d) -> int:
    return (datetime.date(y, m, d) - datetime.date(1970, 1, 1)).days


STARTDATE = date_i(1992, 1, 1)
ENDDATE = date_i(1998, 12, 31)
CURRENTDATE = date_i(1995, 6, 17)
NATIONS = 25
_CODES = {
    "l_returnflag": ("A", "N", "R"),
    "l_linestatus": ("F", "O"),
    "l_shipinstruct": ("COLLECT COD", "DELIVER IN PERSON", "NONE",
                       "TAKE BACK RETURN"),
    "l_shipmode": ("AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"),
    "o_orderstatus": ("F", "O", "P"),
    "o_orderpriority": ("1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED",
                        "5-LOW"),
    "c_mktsegment": ("AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD",
                     "MACHINERY"),
}
MONEY = ("l_quantity", "l_extendedprice", "l_discount", "l_tax",
         "o_totalprice", "c_acctbal")  # DECIMAL(15,2), int64 cents here
_DATES = ("l_shipdate", "l_commitdate", "l_receiptdate", "o_orderdate")
_INT32 = ("l_linenumber", "o_shippriority")
_WORDS = (b"furiously quickly carefully slyly blithely fluffily final "
          b"regular express special pending ironic even bold silent "
          b"packages deposits requests accounts instructions theodolites "
          b"pinto beans foxes ideas dependencies platelets excuses asymptotes "
          b"courts dolphins sleep wake nag haggle cajole detect integrate "
          b"boost use among above across after against along the ").split()


class Text:
    """A column of free text: ``offsets`` (int32, n + 1) into ``data``."""

    def __init__(self, offsets, data):
        self.offsets, self.data = offsets, data

    def __len__(self):
        return len(self.offsets) - 1


def _rng(seed: int, stream: int):
    return np.random.default_rng([int(seed), stream])


def _text(rng, n: int, lo: int, hi: int) -> Text:
    """n texts of lo..hi characters: a seeded stream of words cut at
    seeded lengths (dbgen cuts its texts from one pool of grammar text)."""
    lens = rng.integers(lo, hi + 1, n)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(lens, out=offsets[1:])
    total = int(offsets[-1])
    if total >= 2 ** 31:
        raise ValueError("text column over 2 GiB: needs large_string")
    words = [_WORDS[i] for i in rng.integers(0, len(_WORDS), 1 << 16)]
    pool = np.frombuffer(b" ".join(words), np.uint8)
    reps = -(-total // len(pool))
    return Text(offsets.astype(np.int32), np.tile(pool, reps)[:total])


def _numbered(prefix: bytes, numbers, digits: int = 9) -> Text:
    """``prefix`` + the number, zero-padded: Customer#000000001."""
    n = len(numbers)
    width = len(prefix) + digits
    out = np.empty((n, width), np.uint8)
    out[:, :len(prefix)] = np.frombuffer(prefix, np.uint8)
    v = np.asarray(numbers, np.int64)
    for k in range(digits):
        out[:, width - 1 - k] = 48 + (v // 10 ** k) % 10
    return Text(np.arange(0, (n + 1) * width, width, dtype=np.int32),
                out.reshape(-1))


def order_rows(sf: float) -> int:
    return int(1_500_000 * sf)


def _lines_per_order(n_orders: int):
    return (np.arange(n_orders) % 7 + 1).astype(np.int8)


def rows(table: str, sf: float) -> int:
    """Row count of ``table`` at scale ``sf``: a function of the scale."""
    if table == "lineitem":
        return int(_lines_per_order(order_rows(sf)).sum(dtype=np.int64))
    return {"orders": order_rows(sf),
            "customer": max(int(150_000 * sf), 10)}[table]


def _orders_and_lines(sf: float, seed: int, want: set) -> dict:
    n = order_rows(sf)
    customers = rows("customer", sf)
    r = _rng(seed, 1)
    i = np.arange(1, n + 1, dtype=np.int64)
    orderkey = ((i >> 3) << 5) | (i & 7)  # the first 8 keys of every 32
    orderdate = (STARTDATE + r.integers(0, ENDDATE - 151 - STARTDATE + 1, n)
                 ).astype(np.int32)
    count = r.permutation(_lines_per_order(n))

    owner = np.repeat(np.arange(n, dtype=np.int32), count)  # line -> order
    first = np.zeros(n, np.int64)
    np.cumsum(count[:-1], out=first[1:])
    m = len(owner)
    r = _rng(seed, 2)
    parts = max(int(200_000 * sf), 10)
    supps = max(int(10_000 * sf), 10)
    i4 = np.int32  # every product below stays under 2**31
    partkey = r.integers(1, parts + 1, m, dtype=i4)
    suppkey = (partkey + r.integers(0, 4, m, dtype=i4)
               * (supps // 4 + (partkey - 1) // supps)) % supps + 1
    qty = r.integers(1, 51, m, dtype=i4)
    retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    price = qty * retail  # cents, at most 50 x 209,900
    discount = r.integers(0, 11, m, dtype=i4)  # cents: 0.00 .. 0.10
    tax = r.integers(0, 9, m, dtype=i4)
    shipdate = orderdate[owner] + r.integers(1, 122, m).astype(np.int32)
    receipt = shipdate + r.integers(1, 31, m).astype(np.int32)
    open_ = shipdate > CURRENTDATE
    i8 = np.int64  # what the references read: keys and whole cents as int64
    line = {}
    if "lineitem" in want:
        line = {
            "l_orderkey": orderkey[owner],
            "l_partkey": partkey.astype(i8), "l_suppkey": suppkey.astype(i8),
            "l_linenumber": (np.arange(m, dtype=np.int64) - first[owner] + 1
                             ).astype(np.int32),
            "l_quantity": qty.astype(i8) * 100,
            "l_extendedprice": price.astype(i8),
            "l_discount": discount.astype(i8), "l_tax": tax.astype(i8),
            # R or A at random once received, N otherwise (codes A, N, R)
            "l_returnflag": np.where(receipt <= CURRENTDATE,
                                     r.integers(0, 2, m) * 2, 1
                                     ).astype(np.int8),
            "l_linestatus": open_.astype(np.int8),
            "l_shipdate": shipdate,
            "l_commitdate": orderdate[owner] + r.integers(30, 91, m).astype(
                np.int32),
            "l_receiptdate": receipt,
            "l_shipinstruct": r.integers(0, 4, m).astype(np.int8),
            "l_shipmode": r.integers(0, 7, m).astype(np.int8),
            "l_comment": _text(r, m, 10, 43),
        }
    out = {"lineitem": line}
    if "orders" in want:
        r = _rng(seed, 3)
        buyers = customers - customers // 3  # keys that do not divide by 3
        j = r.integers(0, buyers, n)
        n_open = np.add.reduceat(open_.astype(np.int64), first)
        total = np.add.reduceat((price.astype(i8) * (100 - discount)
                                 // 100) * (100 + tax) // 100, first)
        out["orders"] = {
            "o_orderkey": orderkey,
            "o_custkey": 3 * (j // 2) + j % 2 + 1,
            "o_orderstatus": np.where(n_open == 0, 0, np.where(
                n_open == count, 1, 2)).astype(np.int8),
            "o_totalprice": total,
            "o_orderdate": orderdate,
            "o_orderpriority": r.integers(0, 5, n).astype(np.int8),
            "o_clerk": _numbered(b"Clerk#", r.integers(
                1, max(int(1000 * sf), 1) + 1, n)),
            "o_shippriority": np.zeros(n, np.int32),
            "o_comment": _text(r, n, 19, 78),
        }
    return out


def _customer(sf: float, seed: int) -> dict:
    n = rows("customer", sf)
    r = _rng(seed, 4)
    key = np.arange(1, n + 1, dtype=np.int64)
    nation = r.integers(0, NATIONS, n)
    phone = np.empty((n, 15), np.uint8)
    phone[:] = np.frombuffer(b"00-000-000-0000", np.uint8)
    for col, (v, digits) in {1: (nation + 10, 2), 5: (r.integers(100, 1000, n), 3),
                             9: (r.integers(100, 1000, n), 3),
                             14: (r.integers(1000, 10000, n), 4)}.items():
        for k in range(digits):
            phone[:, col - k] = 48 + (v // 10 ** k) % 10
    return {
        "c_custkey": key,
        "c_name": _numbered(b"Customer#", key),
        "c_address": _text(r, n, 10, 40),
        "c_nationkey": nation,
        "c_phone": Text(np.arange(0, (n + 1) * 15, 15, dtype=np.int32),
                        phone.reshape(-1)),
        "c_acctbal": r.integers(-99999, 1000000, n),
        "c_mktsegment": r.integers(0, 5, n).astype(np.int8),
        "c_comment": _text(r, n, 29, 116),
    }


def make(tables, sf: float, seed: int) -> dict:
    """{table: {column: values}} for the tables asked for. ``orders`` and
    ``lineitem`` are made together (an order's status and total price come
    from its lines); asking for one does not keep the other."""
    want = set(tables)
    out = {}
    if want & {"orders", "lineitem"}:
        both = _orders_and_lines(sf, seed, want)
        out.update({t: both[t] for t in ("orders", "lineitem") if t in want})
    if "customer" in want:
        out["customer"] = _customer(sf, seed)
    return {t: out[t] for t in tables}


def labels(column: str):
    return _CODES[column]


def with_row(table: dict, names, at: int, times: int) -> dict:
    """The columns ``names`` of ``table`` with row ``at`` there ``times``
    times: 0 leaves it out, 2 repeats it. What a query's controls hand its
    reference for a probe match that was dropped or duplicated; only the
    columns the query reads are copied."""
    rows = np.arange(len(table[names[0]]))
    rows = np.delete(rows, at) if times == 0 else np.insert(
        rows, [at] * (times - 1), at)
    return {c: table[c][rows] for c in names}


def _decimal(cents) -> pa.Array:
    """int64 cents -> decimal128(15, 2), through the 16-byte buffer."""
    lo = np.ascontiguousarray(cents, np.int64)
    both = np.empty((len(lo), 2), np.int64)
    both[:, 0] = lo
    both[:, 1] = lo >> 63  # sign extension
    return pa.Array.from_buffers(pa.decimal128(15, 2), len(lo),
                                 [None, pa.py_buffer(both)])


def arrow(columns: dict) -> pa.Table:
    """The Arrow table the program is given: every field NOT NULL."""
    arrays, fields = [], []
    for name, v in columns.items():
        if isinstance(v, Text):
            a = pa.Array.from_buffers(
                pa.string(), len(v),
                [None, pa.py_buffer(v.offsets), pa.py_buffer(v.data)])
        elif name in _CODES:
            a = pa.DictionaryArray.from_arrays(
                pa.array(v, pa.int8()),
                pa.array(list(_CODES[name]), pa.string())).cast(pa.string())
        elif name in MONEY:
            a = _decimal(v)
        elif name in _DATES:
            a = pa.array(v, pa.int32()).cast(pa.date32())
        elif name in _INT32:
            a = pa.array(v, pa.int32())
        else:
            a = pa.array(v, pa.int64())
        arrays.append(a)
        fields.append(pa.field(name, a.type, nullable=False))
    return pa.Table.from_arrays(arrays, schema=pa.schema(fields))


# bytes per value as the device holds the column (money as int64 unscaled,
# fixed-vocabulary strings as int32 dictionary codes); what the least-bytes
# functions of queries/ multiply by
DEVICE_WIDTH = {
    "l_orderkey": 8, "l_partkey": 8, "l_suppkey": 8, "l_linenumber": 4,
    "l_quantity": 8, "l_extendedprice": 8, "l_discount": 8, "l_tax": 8,
    "l_returnflag": 4, "l_linestatus": 4, "l_shipdate": 4, "l_commitdate": 4,
    "l_receiptdate": 4, "l_shipinstruct": 4, "l_shipmode": 4,
    "o_orderkey": 8, "o_custkey": 8, "o_orderstatus": 4, "o_totalprice": 8,
    "o_orderdate": 4, "o_orderpriority": 4, "o_shippriority": 4,
    "c_custkey": 8, "c_nationkey": 8, "c_acctbal": 8, "c_mktsegment": 4,
}
