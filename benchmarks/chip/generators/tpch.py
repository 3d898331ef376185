"""TPC-H lineitem and orders by the rules of the specification's Clause 4.2.3.

``orders`` rows are generated and each order gets 1 to 7 lineitems, so the
lineitem row count is drawn (about 4 per order: 6.0M at scale factor 1,
where dbgen writes 6,001,215).  Every ``l_orderkey`` is an order's key at any
row count.  The part and supplier scales follow the scale factor that the
order count gives (SF = orders / 1,500,000, at least 1).

Dates are days since 1970-01-01 (int32).  Decimals are float32.  Flags and
the other text columns are int32 codes: a one-letter flag is its ASCII code,
a listed value its position in the specification's list, a comment a random
31-bit code in place of the text.
"""
import numpy as np

START = 8035  # 1992-01-01
END_ORDER = 10440  # 1998-08-02: ENDDATE (1998-12-31) less 151 days
CURRENT = 9298  # 1995-06-17
ORDERS_PER_SF = 1_500_000


def orderkeys(n: int) -> np.ndarray:
    """The sparse keys of orders 1..n: the first 8 of every 32."""
    i = np.arange(n, dtype=np.int64)
    return (i // 8) * 32 + i % 8 + 1


def retailprice(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE of each part key, in float64 dollars."""
    return (90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)) / 100.0


def suppkey(partkey: np.ndarray, i: np.ndarray, s: int) -> np.ndarray:
    """The i-th (0..3) supplier of each part key among ``s`` suppliers."""
    return (partkey + i * (s // 4 + (partkey - 1) // s)) % s + 1


def generate(rng: np.random.Generator, orders: int) -> dict:
    n = orders
    sf = max(1, n // ORDERS_PER_SF)
    n_cust, n_part, n_supp, n_clerk = 150_000 * sf, 200_000 * sf, 10_000 * sf, 1_000 * sf

    o_orderkey = orderkeys(n)
    # Customer keys divisible by 3 place no orders.
    o_custkey = rng.integers(1, n_cust * 2 // 3 + 1, n)
    o_custkey = o_custkey + (o_custkey - 1) // 2
    o_orderdate = rng.integers(START, END_ORDER + 1, n)
    o_orderpriority = rng.integers(1, 6, n)
    o_clerk = rng.integers(1, n_clerk + 1, n)
    o_comment = rng.integers(0, 2**31 - 1, n)

    per_order = rng.integers(1, 8, n)
    m = int(per_order.sum())
    owner = np.repeat(np.arange(n), per_order)
    first = np.cumsum(per_order) - per_order
    l_linenumber = np.arange(m) - first[owner] + 1
    l_partkey = rng.integers(1, n_part + 1, m)
    l_suppkey = suppkey(l_partkey, rng.integers(0, 4, m), n_supp)
    l_quantity = rng.integers(1, 51, m)
    l_extendedprice = np.round(l_quantity * retailprice(l_partkey), 2)
    l_discount = rng.integers(0, 11, m) / 100.0
    l_tax = rng.integers(0, 9, m) / 100.0
    orderdate = o_orderdate[owner]
    l_shipdate = orderdate + rng.integers(1, 122, m)
    l_commitdate = orderdate + rng.integers(30, 91, m)
    l_receiptdate = l_shipdate + rng.integers(1, 31, m)
    l_returnflag = np.where(l_receiptdate <= CURRENT,
                            np.where(rng.integers(0, 2, m) == 0, ord("R"), ord("A")), ord("N"))
    l_linestatus = np.where(l_shipdate > CURRENT, ord("O"), ord("F"))
    l_shipinstruct = rng.integers(0, 4, m)
    l_shipmode = rng.integers(0, 7, m)
    l_comment = rng.integers(0, 2**31 - 1, m)

    # O_TOTALPRICE and O_ORDERSTATUS summarize the order's lineitems.
    charge = l_extendedprice * (1 + l_tax) * (1 - l_discount)
    o_totalprice = np.round(np.bincount(owner, weights=charge, minlength=n), 2)
    n_open = np.bincount(owner, weights=l_linestatus == ord("O"), minlength=n)
    o_orderstatus = np.where(n_open == per_order, ord("O"),
                             np.where(n_open == 0, ord("F"), ord("P")))

    lineitem = dict(
        l_orderkey=o_orderkey[owner], l_partkey=l_partkey, l_suppkey=l_suppkey,
        l_linenumber=l_linenumber, l_quantity=l_quantity.astype(np.float32),
        l_extendedprice=l_extendedprice.astype(np.float32),
        l_discount=l_discount.astype(np.float32), l_tax=l_tax.astype(np.float32),
        l_returnflag=l_returnflag, l_linestatus=l_linestatus, l_shipdate=l_shipdate,
        l_commitdate=l_commitdate, l_receiptdate=l_receiptdate,
        l_shipinstruct=l_shipinstruct, l_shipmode=l_shipmode, l_comment=l_comment)
    orders_cols = dict(
        o_orderkey=o_orderkey, o_custkey=o_custkey, o_orderstatus=o_orderstatus,
        o_totalprice=o_totalprice.astype(np.float32), o_orderdate=o_orderdate,
        o_orderpriority=o_orderpriority, o_clerk=o_clerk,
        o_shippriority=np.zeros(n, dtype=np.int32), o_comment=o_comment)

    def typed(cols):
        return {k: v if v.dtype == np.float32 else v.astype(np.int32) for k, v in cols.items()}

    return {"lineitem": (typed(lineitem), ("l_orderkey", "l_linenumber")),
            "orders": (typed(orders_cols), ("o_orderkey",))}
