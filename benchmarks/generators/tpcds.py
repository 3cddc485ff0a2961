"""TPC-DS tables made from a seed — ``store_sales``, ``date_dim`` and
``store`` — in the shapes of the specification: every column of each table
at its type, SF1's row counts, and files written as a user's writer writes
them: ``pyarrow.parquet.write_table(table, path)`` with no option.

Not dsdgen: the draws come from numpy's PCG64 seeded with ``--seed``, and
each departure is listed in ``configs/tpcds_sf1_parquet.json`` under
``assumed``. What dsdgen fixes, this keeps:

- ``date_dim`` is the calendar itself: one row a day from 1900-01-02,
  ``d_date_sk`` its Julian day number, every other column derived from the
  date. No seed moves it.
- ``store`` holds 12 rows over 6 business ids, the slowly changing
  dimension's revisions 1, 2, 3, 1, 2, 3; its texts and gmt offsets are
  fixed by the business id, its other numbers drawn from the seed.
- ``store_sales`` is a list of tickets, 8 to 16 lines each (a seeded
  permutation of one fixed multiset, so no seed changes a row count): a
  ticket's date, time, customer, demographics, address and store are its
  lines' own, each line an item of its own. Every column but the primary
  key (``ss_item_sk``, ``ss_ticket_number``) is then null at
  ``NULL_RATE``, drawn per row and column.

Money is float64 holding whole cents. ``ensure`` is the entry the harness
calls; see ``benchmarks/README.md``.
"""

import datetime
import importlib.util
import os

import numpy as np
import pyarrow as pa

_spec = importlib.util.spec_from_file_location(
    "bench_tpch_writer_defaults",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "tpch_writer_defaults.py"))
writer_defaults = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(writer_defaults)
_rng, _date = writer_defaults.tpch._rng, writer_defaults.tpch._date

FIXED = ("date_dim", "store")        # rows that no scale changes
TABLES = ("store_sales", "date_dim", "store")
NULL_RATE = 0.04
FIRST_DAY = datetime.date(1900, 1, 2)
FIRST_SK = 2415022                   # the Julian day number of FIRST_DAY
# dsdgen's sales dates: 1998-01-02 .. 2003-01-02, as Julian day numbers
SOLD_FIRST, SOLD_LAST = 2450816, 2452642
LINES = (8, 16)                      # lines a ticket
# SF1 sizes of the dimensions a sale points into
ITEMS, CUSTOMERS, CDEMOS, HDEMOS, ADDRESSES, PROMOS, TIMES = (
    18000, 100000, 1920800, 7200, 50000, 300, 86400)
NOT_NULL = ("ss_item_sk", "ss_ticket_number")
DAYS = ("Monday", "Tuesday", "Wednesday", "Thursday", "Friday", "Saturday",
        "Sunday")                    # datetime's weekday() order
SYLLABLES = ("bar", "ought", "able", "pri", "pres", "ese", "anti", "cally",
             "ation", "eing")


def row_counts(tables: dict, scale: float = 1.0) -> dict:
    """Rows of every table: the configuration's at scale 1; a trial below
    the cell's size shrinks the fact table alone."""
    return {t: (rows if t in FIXED or scale == 1.0
                else max(int(round(rows * scale)), 40))
            for t, rows in tables.items()}


def bkey(keys) -> pa.Array:
    """dsdgen's 16-character business ids: eight 'A's, then the key in
    base 16, lowest digit first, with the letters A..P for digits."""
    keys = np.asarray(keys, np.int64)
    digits = (keys[:, None] >> (4 * np.arange(8))) & 15
    chars = np.concatenate([np.full((len(keys), 8), ord("A")),
                            ord("A") + digits], axis=1).astype(np.uint8)
    return pa.array([row.tobytes().decode() for row in chars], pa.string())


def _ticket_lines(n_tickets: int, n_rows: int) -> np.ndarray:
    """A fixed multiset over 8..16, as near uniform as its sum allows."""
    lo, hi = LINES
    counts = np.arange(n_tickets, dtype=np.int64) % (hi - lo + 1) + lo
    diff = int(n_rows - counts.sum())
    step = 1 if diff > 0 else -1
    while diff:
        room = np.flatnonzero(counts < hi if step > 0 else counts > lo)
        room = room[:abs(diff)]
        counts[room] += step
        diff -= step * len(room)
    return counts


def _nullable(rng, values, n: int) -> pa.Array:
    """The column with each row null at ``NULL_RATE``."""
    return pa.array(values, mask=rng.random(n) < NULL_RATE)


def gen_store_sales(n_rows: int, seed: int) -> pa.Table:
    """Tickets of 8 to 16 lines; prices as dsdgen derives them: list price
    a markup over the wholesale cost, sales price a discount off it, the
    extended amounts the line's quantity times them."""
    i64 = np.int64
    n_tickets = max(n_rows // 12, 1)
    rng = _rng(seed, 0)
    lines = rng.permutation(_ticket_lines(n_tickets, n_rows))
    ticket = np.repeat(np.arange(n_tickets, dtype=i64), lines)
    first = np.concatenate([[0], np.cumsum(lines)[:-1]])
    line = np.arange(n_rows, dtype=i64) - first[ticket]

    def per_ticket(lo, hi):
        return rng.integers(lo, hi + 1, n_tickets).astype(i64)[ticket]
    sold_date = per_ticket(SOLD_FIRST, SOLD_LAST)
    sold_time = per_ticket(0, TIMES - 1)
    customer = per_ticket(1, CUSTOMERS)
    cdemo = per_ticket(1, CDEMOS)
    hdemo = per_ticket(1, HDEMOS)
    addr = per_ticket(1, ADDRESSES)
    store = per_ticket(1, 12)
    # a ticket's items: consecutive entries of one seeded permutation
    items = rng.permutation(ITEMS).astype(i64) + 1
    item = items[(per_ticket(0, ITEMS - 1) + line) % ITEMS]
    promo = rng.integers(1, PROMOS + 1, n_rows).astype(i64)

    rng = _rng(seed, 1)
    quantity = rng.integers(1, 101, n_rows)
    wholesale = rng.integers(100, 10001, n_rows)               # cents
    markup = rng.integers(0, 201, n_rows)                      # percent
    discount = rng.integers(0, 101, n_rows)                    # percent
    tax_pct = rng.integers(0, 10, n_rows)                      # percent
    coupon = np.where(rng.random(n_rows) < 0.2,
                      rng.integers(0, 101, n_rows), 0)         # percent
    list_price = wholesale * (100 + markup) // 100
    sales_price = list_price * (100 - discount) // 100
    ext_sales = quantity * sales_price
    ext_wholesale = quantity * wholesale
    ext_list = quantity * list_price
    ext_tax = ext_sales * tax_pct // 100
    coupon_amt = ext_sales * coupon // 100
    net_paid = ext_sales - coupon_amt
    columns = {
        "ss_sold_date_sk": sold_date,
        "ss_sold_time_sk": sold_time,
        "ss_item_sk": item,
        "ss_customer_sk": customer,
        "ss_cdemo_sk": cdemo,
        "ss_hdemo_sk": hdemo,
        "ss_addr_sk": addr,
        "ss_store_sk": store,
        "ss_promo_sk": promo,
        "ss_ticket_number": ticket + 1,
        "ss_quantity": quantity.astype(np.int32),
        "ss_wholesale_cost": wholesale / 100.0,
        "ss_list_price": list_price / 100.0,
        "ss_sales_price": sales_price / 100.0,
        "ss_ext_discount_amt": (ext_list - ext_sales) / 100.0,
        "ss_ext_sales_price": ext_sales / 100.0,
        "ss_ext_wholesale_cost": ext_wholesale / 100.0,
        "ss_ext_list_price": ext_list / 100.0,
        "ss_ext_tax": ext_tax / 100.0,
        "ss_coupon_amt": coupon_amt / 100.0,
        "ss_net_paid": net_paid / 100.0,
        "ss_net_paid_inc_tax": (net_paid + ext_tax) / 100.0,
        "ss_net_profit": (net_paid - ext_wholesale) / 100.0,
    }
    rng = _rng(seed, 2)
    return pa.table({
        name: (pa.array(values) if name in NOT_NULL
               else _nullable(rng, values, n_rows))
        for name, values in columns.items()})


def gen_date_dim(rows: int) -> pa.Table:
    """The calendar from 1900-01-02, one row a day."""
    i64 = np.int64
    sk = FIRST_SK + np.arange(rows, dtype=i64)
    dates = [FIRST_DAY + datetime.timedelta(days=k) for k in range(rows)]
    year = np.array([d.year for d in dates], np.int32)
    moy = np.array([d.month for d in dates], np.int32)
    dom = np.array([d.day for d in dates], np.int32)
    weekday = np.array([d.weekday() for d in dates], np.int32)  # Monday 0
    dow = (weekday + 1) % 7                                     # Sunday 0
    qoy = (moy - 1) // 3 + 1
    month_seq = (year - 1900) * 12 + moy - 1
    quarter_seq = (year - 1900) * 4 + qoy
    week_seq = (np.arange(rows) + (FIRST_DAY.weekday() + 1) % 7) // 7 + 1
    first_dom = sk - dom + 1
    month_len = np.array([((d.replace(day=28) + datetime.timedelta(days=4))
                           .replace(day=1) - datetime.timedelta(days=1)).day
                          for d in dates], np.int32)
    holiday = np.array([(d.month, d.day) in ((1, 1), (7, 4), (12, 25))
                        for d in dates])
    yes_no = np.array(["N", "Y"])
    epoch = np.array([(d - datetime.date(1970, 1, 1)).days for d in dates])
    return pa.table({
        "d_date_sk": sk,
        "d_date_id": bkey(sk),
        "d_date": _date(epoch),
        "d_month_seq": month_seq.astype(np.int32),
        "d_week_seq": week_seq.astype(np.int32),
        "d_quarter_seq": quarter_seq.astype(np.int32),
        "d_year": year,
        "d_dow": dow.astype(np.int32),
        "d_moy": moy,
        "d_dom": dom,
        "d_qoy": qoy.astype(np.int32),
        "d_fy_year": year,
        "d_fy_quarter_seq": quarter_seq.astype(np.int32),
        "d_fy_week_seq": week_seq.astype(np.int32),
        "d_day_name": pa.array(np.array(DAYS)[weekday], pa.string()),
        "d_quarter_name": pa.array([f"{y}Q{q}" for y, q in
                                    zip(year.tolist(), qoy.tolist())],
                                   pa.string()),
        "d_holiday": pa.array(yes_no[holiday.astype(int)], pa.string()),
        "d_weekend": pa.array(yes_no[(weekday >= 5).astype(int)],
                              pa.string()),
        "d_following_holiday": pa.array(
            yes_no[np.r_[False, holiday[:-1]].astype(int)], pa.string()),
        "d_first_dom": first_dom.astype(np.int32),
        "d_last_dom": (first_dom + month_len - 1).astype(np.int32),
        "d_same_day_ly": (sk - 365).astype(np.int32),
        "d_same_day_lq": (sk - 91).astype(np.int32),
        "d_current_day": pa.array(np.full(rows, "N"), pa.string()),
        "d_current_week": pa.array(np.full(rows, "N"), pa.string()),
        "d_current_month": pa.array(np.full(rows, "N"), pa.string()),
        "d_current_quarter": pa.array(np.full(rows, "N"), pa.string()),
        "d_current_year": pa.array(np.full(rows, "N"), pa.string()),
    })


def _store_revisions(rows: int):
    """(business id, revision, revisions of that id) of each row: ids take
    1, 2, 3, 1, 2, 3, ... revisions in turn."""
    out, business = [], 0
    while len(out) < rows:
        business += 1
        count = (business - 1) % 3 + 1
        out += [(business, r, count) for r in range(count)]
    return out[:rows]


def gen_store(rows: int, seed: int) -> pa.Table:
    """The slowly changing store dimension. Texts and gmt offsets are the
    business id's; a third revision renames its store."""
    rng = _rng(seed, 3)
    rev = _store_revisions(rows)
    business = np.array([b for b, _, _ in rev], np.int64)
    # revision starts and ends (dsdgen's three dates), epoch days
    starts = [datetime.date(1997, 3, 13), datetime.date(1999, 3, 14),
              datetime.date(2001, 3, 13)]
    epoch = datetime.date(1970, 1, 1)

    def days(d):
        return (d - epoch).days
    start = [days(starts[r]) if count > 1 else days(starts[0])
             for _, r, count in rev]
    end = [days(starts[r + 1]) - 1 if r + 1 < count else None
           for _, r, count in rev]
    name = [SYLLABLES[(b + (r == 2)) % len(SYLLABLES)] for b, r, _ in rev]
    texts = {"s_hours": ("8AM-4PM", "8AM-8AM", "8AM-12AM"),
             "s_manager": ("William Ward", "Scott Smith", "Edwin Adams",
                           "David Thomas", "Robert Jones", "Charles Bartley"),
             "s_street_name": ("Main", "Oak", "Park", "Elm", "Maple",
                               "Cedar"),
             "s_street_type": ("Street", "Avenue", "Blvd", "Road", "Way",
                               "Lane"),
             "s_city": ("Midway", "Fairview", "Oak Grove", "Five Points",
                        "Pleasant Hill", "Riverside")}

    def by_id(key):
        pool = texts[key]
        return pa.array([pool[(b - 1) % len(pool)] for b in business],
                        pa.string())
    n = rows
    return pa.table({
        "s_store_sk": np.arange(1, n + 1, dtype=np.int64),
        "s_store_id": bkey(business),
        "s_rec_start_date": _date(start),
        "s_rec_end_date": pa.array(end, pa.date32()),
        "s_closed_date_sk": pa.array(
            [SOLD_FIRST + 400 * b if b % 4 == 0 else None
             for b in business.tolist()], pa.int64()),
        "s_store_name": pa.array(name, pa.string()),
        "s_number_employees": rng.integers(200, 301, n).astype(np.int32),
        "s_floor_space": rng.integers(5000000, 10000001, n).astype(np.int32),
        "s_hours": by_id("s_hours"),
        "s_manager": by_id("s_manager"),
        "s_market_id": ((business - 1) % 10 + 1).astype(np.int32),
        "s_geography_class": pa.array(["Unknown"] * n, pa.string()),
        "s_market_desc": pa.array([f"Market {b} of the stores' region"
                                   for b in business.tolist()], pa.string()),
        "s_market_manager": by_id("s_manager"),
        "s_division_id": np.ones(n, np.int32),
        "s_division_name": pa.array(["Unknown"] * n, pa.string()),
        "s_company_id": np.ones(n, np.int32),
        "s_company_name": pa.array(["Unknown"] * n, pa.string()),
        "s_street_number": pa.array([str(100 + 37 * b)
                                     for b in business.tolist()],
                                    pa.string()),
        "s_street_name": by_id("s_street_name"),
        "s_street_type": by_id("s_street_type"),
        "s_suite_number": pa.array([f"Suite {10 * b}"
                                    for b in business.tolist()], pa.string()),
        "s_city": by_id("s_city"),
        "s_county": pa.array(["Williamson County"] * n, pa.string()),
        "s_state": pa.array(["TN"] * n, pa.string()),
        "s_zip": pa.array([f"{31904 + b:05d}" for b in business.tolist()],
                          pa.string()),
        "s_country": pa.array(["United States"] * n, pa.string()),
        # four of six business ids in the -5 zone, two in -6
        "s_gmt_offset": np.where(business % 3 == 2, -6.0, -5.0),
        "s_tax_precentage": rng.integers(0, 12, n) / 100.0,
    })


def ensure(data_dir: str, config: dict, tables, seed: int,
           scale: float = 1.0):
    """({table: parquet path}, {table: rows}) for the tables asked for,
    under ``data_dir/tpcds_<rows>_seed<S>/``; a table is made and written
    once per (size, seed) and found again by later runs of that seed."""
    n = row_counts(config["tables"], scale)
    out = os.path.join(data_dir, f"tpcds_{n['store_sales']}_seed{seed}")
    os.makedirs(out, exist_ok=True)
    paths = {t: os.path.join(out, f"{t}.parquet") for t in tables}
    for name, path in paths.items():
        if os.path.exists(path):
            continue
        table = (gen_store_sales(n[name], seed) if name == "store_sales"
                 else gen_date_dim(n[name]) if name == "date_dim"
                 else gen_store(n[name], seed))
        writer_defaults.write_default(table, path)
    return paths, n
