"""TPC-H tables made from a seed, in the shapes of the specification
(v3.0.1, clause 4.2.3): every column of every table at its type and
width, clause 4.2.5's cardinalities, sparse order keys, 1 to 7 lines per
order, o_orderdate over 1992-01-01..1998-08-02, l_shipdate = o_orderdate
+ 1..121, flags and statuses derived from the dates, prices derived from
the part key, o_totalprice and o_orderstatus derived from the lines.

Not dbgen: the draws come from numpy's PCG64 seeded with ``--seed``, and
text comes from a seeded pool of the grammar's words. No seed changes a
shape the engine sees. Row counts come from the configuration's ``tables``:
the lines per order are a seeded permutation of one fixed multiset over
1..7 whose sum is the lineitem count. And the engine compiles its programs
for the exact number of distinct strings in a row group, so that number is
fixed too: a comment column holds ``TEXTS`` distinct texts, each present in
every row group, and names, addresses and phones are distinct by
construction (p_name alone may repeat; no cell reads part). Departures are
listed in ``configs/*.json`` under ``assumed``.

``ensure`` is the entry the harness calls; see ``benchmarks/README.md``.
"""

import os

import numpy as np
import pyarrow as pa

import columns

# 1992-01-01, 1995-06-17, 1998-12-31 as days since 1970-01-01
STARTDATE, CURRENTDATE, ENDDATE = 8035, 9298, 10591
FIXED = {"nation": 25, "region": 5}
TABLES = ("lineitem", "orders", "customer", "supplier", "part", "partsupp",
          "nation", "region")

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
TYPES = [f"{a} {b} {c}"
         for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
         for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
         for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")]
CONTAINERS = [f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
              for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM")]
MFGRS = [f"Manufacturer#{m}" for m in range(1, 6)]
BRANDS = [f"Brand#{m}{n}" for m in range(1, 6) for n in range(1, 6)]
COLORS = """almond antique aquamarine azure beige bisque black blanched blue
blush brown burlywood burnished chartreuse chiffon chocolate coral cornflower
cornsilk cream cyan dark deep dim dodger drab firebrick floral forest frosted
gainsboro ghost goldenrod green grey honeydew hot indian ivory khaki lace
lavender lawn lemon light lime linen magenta maroon medium metallic midnight
mint misty moccasin navajo navy olive orange orchid pale papaya peach peru
pink plum powder puff purple red rose rosy royal saddle salmon sandy seashell
sienna sky slate smoke snow spring steel tan thistle tomato turquoise violet
wheat white yellow""".split()
NATIONS = [("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
           ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
           ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
           ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
           ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
           ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
           ("UNITED KINGDOM", 3), ("UNITED STATES", 1)]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
WORDS = """foxes ideas theodolites pinto beans instructions dependencies
excuses platelets asymptotes courts dolphins multipliers sauternes warthogs
frets dinos attainments somas patterns forges braids frays warhorses dugouts
notornis epitaphs pearls tithes waters orbits gifts sheaves depths sentiments
decoys realms pains grouches escapades packages requests accounts deposits
sleep wake are cajole haggle nag use boost affix detect integrate maintain nod
was lose sublate solve thrash promise engage hinder print x-ray breach eat grow
impress mold poach serve run dazzle snooze doze unwind kindle play hang believe
doubt furious sly careful blithe quick fluffy slow quiet ruthless thin close
dogged daring brave stealthy permanent enticing idle busy regular final ironic
even bold silent special pending unusual express sometimes always never
furiously slyly carefully blithely quickly fluffily slowly quietly ruthlessly
thinly closely doggedly daringly bravely stealthily permanently enticingly idly
busily regularly finally ironically evenly boldly silently about above
according to across after against along alongside of among around at atop
before behind beneath beside besides between beyond by despite during except
for from in place of inside instead of into near on outside over past since
through throughout toward under until up upon without with within""".split()
TERMINATORS = [".", ";", ":", "?", "!", "--"]
POOL_BYTES = 1 << 20
TEXTS = 4096        # distinct comments of a column
PHONES = 900 * 900 * 9000
ALPHABET = np.frombuffer(
    b"0123456789abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ, ",
    np.uint8)


def row_counts(tables: dict, scale: float = 1.0) -> dict:
    """Rows of every table: the configuration's at scale 1; a trial below
    the cell's size shrinks every table but the two fixed ones alike."""
    n = {t: (rows if t in FIXED or scale == 1.0
             else max(int(round(rows * scale)), 40))
         for t, rows in tables.items()}
    if "partsupp" in n and "part" in n:
        n["partsupp"] = 4 * n["part"]
    if "lineitem" in n and "orders" in n:
        n["lineitem"] = min(max(n["lineitem"], n["orders"]), 7 * n["orders"])
    return n


def _rng(seed: int, stream: int):
    return np.random.default_rng([int(seed), stream])


def _pick(rng, pool, n, codes=None):
    """n strings from a small pool, uniformly or by the given codes; kept
    as a dictionary, which the parquet writer takes as it is."""
    if codes is None:
        codes = rng.integers(0, len(pool), n)
    return pa.DictionaryArray.from_arrays(
        np.asarray(codes, np.int32), pa.array(pool, pa.string()))


def _strings(offsets, data) -> pa.Array:
    return pa.Array.from_buffers(
        pa.string(), len(offsets) - 1,
        [None, pa.py_buffer(offsets.astype(np.int32)), pa.py_buffer(data)])


def _text_pool(rng) -> np.ndarray:
    """The seeded pool comments are cut from: words of the specification's
    grammar, a terminator after every 4 to 9 of them."""
    out, size = [], 0
    words = rng.integers(0, len(WORDS), POOL_BYTES // 4)
    stops = rng.integers(4, 10, len(words))
    ends = rng.integers(0, len(TERMINATORS), len(words))
    run = 0
    for w, stop, end in zip(words.tolist(), stops.tolist(), ends.tolist()):
        piece = WORDS[w]
        run += 1
        if run >= stop:
            piece, run = piece + TERMINATORS[end], 0
        out.append(piece)
        size += len(piece) + 1
        if size >= POOL_BYTES:
            break
    return np.frombuffer(" ".join(out).encode()[:POOL_BYTES], np.uint8)


def _text(rng, pool, n, lo, hi, group_rows) -> pa.Array:
    """n comments of lo..hi characters (clause 4.2.2.10's lengths) out of
    ``TEXTS`` distinct ones, cut from the pool one after another from a
    seeded place; every row group of ``group_rows`` rows holds each of them
    (all it has room for), in an order of its own."""
    texts, at = {}, int(rng.integers(0, len(pool)))
    text = np.roll(pool, -at).tobytes().decode() * 2
    at = 0
    for length in rng.integers(lo, hi + 1, 4 * TEXTS).tolist():
        texts.setdefault(text[at:at + length])
        at += length
        if len(texts) == TEXTS:
            break
    codes = np.arange(n, dtype=np.int32) % group_rows % len(texts)
    for lo_row in range(0, n, group_rows):
        rng.shuffle(codes[lo_row:lo_row + group_rows])
    return pa.DictionaryArray.from_arrays(codes,
                                          pa.array(list(texts), pa.string()))


def _vstring(rng, n, lo, hi) -> pa.Array:
    """n random strings of lo..hi characters (clause 4.2.2.7)."""
    offsets = np.concatenate([[0], np.cumsum(rng.integers(lo, hi + 1, n))])
    return _strings(offsets, ALPHABET[rng.integers(0, len(ALPHABET),
                                                   int(offsets[-1]))])


def _numbered(prefix, keys) -> pa.Array:
    return pa.array([f"{prefix}#{k:09d}" for k in keys.tolist()], pa.string())


def _phones(rng, nation, keys) -> pa.Array:
    """Clause 4.2.2.9's phone numbers, no two alike: the local number is a
    bijection of the key (a prime multiplier, a seeded offset)."""
    local = (keys * 2654435761 + int(rng.integers(0, PHONES))) % PHONES
    return pa.array([f"{k + 10}-{x // 8100000 + 100}-{x // 9000 % 900 + 100}"
                     f"-{x % 9000 + 1000}"
                     for k, x in zip(nation.tolist(), local.tolist())],
                    pa.string())


def _cents(rng, lo, hi, n) -> np.ndarray:
    """Money drawn in whole cents, as float64 (see ``assumed``)."""
    return rng.integers(lo, hi + 1, n) / 100.0


def _date(days) -> pa.Array:
    return pa.array(np.asarray(days, np.int32), pa.date32())


def _retail_cents(partkey) -> np.ndarray:
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def _supplier_of(partkey, i, n_supplier) -> np.ndarray:
    """Clause 4.2.3's supplier of a part: the i-th of its four."""
    s = n_supplier
    return (partkey + i * (s // 4 + (partkey - 1) // s)) % s + 1


def _lines_per_order(n_orders: int, n_lines: int) -> np.ndarray:
    """A fixed multiset over 1..7, as near uniform as its sum allows."""
    counts = np.arange(n_orders, dtype=np.int64) % 7 + 1
    diff = int(n_lines - counts.sum())
    step = 1 if diff > 0 else -1
    while diff:
        room = np.flatnonzero(counts < 7 if step > 0 else counts > 1)
        room = room[:abs(diff)]
        counts[room] += step
        diff -= step * len(room)
    return counts


def gen_sales(n: dict, seed: int, want, group_rows: int) -> dict:
    """orders and lineitem, drawn together: a line's dates follow its
    order's, an order's total and status follow its lines."""
    n_o, i64 = n["orders"], np.int64
    rng = _rng(seed, 0)
    idx = np.arange(n_o, dtype=i64)
    orderkey = idx // 8 * 32 + idx % 8 + 1        # 8 of every 32 keys used
    orderdate = rng.integers(STARTDATE, ENDDATE - 151 + 1, n_o)
    lines = rng.permutation(_lines_per_order(n_o, n["lineitem"]))
    starts = np.concatenate([[0], np.cumsum(lines)[:-1]])
    n_l = int(lines.sum())

    rng = _rng(seed, 1)
    of_order = np.repeat(idx, lines)
    partkey = rng.integers(1, n["part"] + 1, n_l).astype(i64)
    quantity = rng.integers(1, 51, n_l)
    discount = rng.integers(0, 11, n_l) / 100.0
    tax = rng.integers(0, 9, n_l) / 100.0
    price = quantity * _retail_cents(partkey) / 100.0
    ship = orderdate[of_order] + rng.integers(1, 122, n_l)
    commit = orderdate[of_order] + rng.integers(30, 91, n_l)
    receipt = ship + rng.integers(1, 31, n_l)
    shipped = ship <= CURRENTDATE
    out = {}
    if "lineitem" in want:
        pool = _text_pool(rng)
        returned = np.where(receipt <= CURRENTDATE,
                            rng.integers(0, 2, n_l) * 2, 1)   # A or R, else N
        out["lineitem"] = pa.table({
            "l_orderkey": orderkey[of_order],
            "l_partkey": partkey,
            "l_suppkey": _supplier_of(partkey, rng.integers(0, 4, n_l),
                                      n["supplier"]),
            "l_linenumber": (np.arange(n_l) - starts[of_order]
                             + 1).astype(np.int32),
            "l_quantity": quantity.astype(np.float64),
            "l_extendedprice": price,
            "l_discount": discount,
            "l_tax": tax,
            "l_returnflag": _pick(rng, ["A", "N", "R"], n_l, returned),
            "l_linestatus": _pick(rng, ["F", "O"], n_l, ~shipped),
            "l_shipdate": _date(ship),
            "l_commitdate": _date(commit),
            "l_receiptdate": _date(receipt),
            "l_shipinstruct": _pick(rng, INSTRUCTIONS, n_l),
            "l_shipmode": _pick(rng, MODES, n_l),
            "l_comment": _text(rng, pool, n_l, 10, 43, group_rows),
        })
    if "orders" in want:
        rng = _rng(seed, 2)
        pool = _text_pool(rng)
        open_lines = np.add.reduceat((~shipped).astype(i64), starts)
        status = np.where(open_lines == 0, 0, np.where(open_lines == lines,
                                                       1, 2))
        total = np.add.reduceat(price * (1.0 + tax) * (1.0 - discount),
                                starts)
        buyers = rng.integers(0, n["customer"] * 2 // 3, n_o)
        clerks = max(n_o // 1500, 1)
        out["orders"] = pa.table({
            "o_orderkey": orderkey,
            # no customer whose key is a multiple of 3 places an order
            "o_custkey": (buyers + buyers // 2 + 1).astype(i64),
            "o_orderstatus": _pick(rng, ["F", "O", "P"], n_o, status),
            "o_totalprice": np.round(total, 2),
            "o_orderdate": _date(orderdate),
            "o_orderpriority": _pick(rng, PRIORITIES, n_o),
            "o_clerk": _pick(rng, [f"Clerk#{k:09d}"
                                   for k in range(1, clerks + 1)], n_o),
            "o_shippriority": np.zeros(n_o, np.int32),
            "o_comment": _text(rng, pool, n_o, 19, 78, group_rows),
        })
    return out


def gen_table(name: str, n: dict, seed: int, group_rows: int):
    """One table that depends on no other, as a pyarrow Table."""
    i64 = np.int64
    rng = _rng(seed, 1 + TABLES.index(name))   # 0..2 are gen_sales'
    rows = n[name]
    keys = np.arange(1, rows + 1, dtype=i64)
    if name == "customer":
        nation = rng.integers(0, 25, rows).astype(i64)
        return pa.table({
            "c_custkey": keys,
            "c_name": _numbered("Customer", keys),
            "c_address": _vstring(rng, rows, 10, 40),
            "c_nationkey": nation,
            "c_phone": _phones(rng, nation, keys),
            "c_acctbal": _cents(rng, -99999, 999999, rows),
            "c_mktsegment": _pick(rng, SEGMENTS, rows),
            "c_comment": _text(rng, _text_pool(rng), rows, 29, 116, group_rows),
        })
    if name == "supplier":
        nation = rng.integers(0, 25, rows).astype(i64)
        comments = _text(rng, _text_pool(rng), rows, 25, 100,
                         group_rows).to_pylist()
        marked = rng.permutation(rows)[:min(10 * rows // 10000, rows)]
        for j, row in enumerate(marked.tolist()):   # 5 + 5 per scale factor
            word = "Complaints" if j % 2 else "Recommends"
            comments[row] = f"Customer {word} " + comments[row][20:]
        return pa.table({
            "s_suppkey": keys,
            "s_name": _numbered("Supplier", keys),
            "s_address": _vstring(rng, rows, 10, 40),
            "s_nationkey": nation,
            "s_phone": _phones(rng, nation, keys),
            "s_acctbal": _cents(rng, -99999, 999999, rows),
            "s_comment": pa.array(comments, pa.string()),
        })
    if name == "part":
        mfgr = rng.integers(0, 5, rows)
        five = np.argsort(rng.random((rows, len(COLORS))), axis=1)[:, :5]
        return pa.table({
            "p_partkey": keys,
            "p_name": pa.array([" ".join(COLORS[c] for c in row)
                                for row in five.tolist()], pa.string()),
            "p_mfgr": _pick(rng, MFGRS, rows, mfgr),
            "p_brand": _pick(rng, BRANDS, rows,
                             mfgr * 5 + rng.integers(0, 5, rows)),
            "p_type": _pick(rng, TYPES, rows),
            "p_size": rng.integers(1, 51, rows).astype(np.int32),
            "p_container": _pick(rng, CONTAINERS, rows),
            "p_retailprice": _retail_cents(keys) / 100.0,
            "p_comment": _text(rng, _text_pool(rng), rows, 5, 22, group_rows),
        })
    if name == "partsupp":
        partkey = np.repeat(np.arange(1, n["part"] + 1, dtype=i64), 4)
        return pa.table({
            "ps_partkey": partkey,
            "ps_suppkey": _supplier_of(partkey, np.arange(rows) % 4,
                                       n["supplier"]),
            "ps_availqty": rng.integers(1, 10000, rows).astype(np.int32),
            "ps_supplycost": _cents(rng, 100, 100000, rows),
            "ps_comment": _text(rng, _text_pool(rng), rows, 49, 198, group_rows),
        })
    if name == "nation":
        return pa.table({
            "n_nationkey": np.arange(25, dtype=i64),
            "n_name": pa.array([name for name, _ in NATIONS]),
            "n_regionkey": np.array([r for _, r in NATIONS], i64),
            "n_comment": _text(rng, _text_pool(rng), 25, 31, 114, group_rows),
        })
    if name == "region":
        return pa.table({
            "r_regionkey": np.arange(5, dtype=i64),
            "r_name": pa.array(REGIONS),
            "r_comment": _text(rng, _text_pool(rng), 5, 31, 115, group_rows),
        })
    raise KeyError(f"no such table: {name}")


def ensure(data_dir: str, config: dict, tables, seed: int,
           scale: float = 1.0):
    """({table: parquet path}, {table: rows}) for the tables asked for,
    under ``data_dir/tpch_<rows>_seed<S>/``; a table is made and written
    once per (size, seed) and found again by later runs of that seed."""
    n = row_counts(config["tables"], scale)
    out = os.path.join(data_dir, f"tpch_{n['lineitem']}_seed{seed}")
    os.makedirs(out, exist_ok=True)
    paths = {t: os.path.join(out, f"{t}.parquet") for t in tables}
    missing = [t for t, p in paths.items() if not os.path.exists(p)]
    group_rows = config["storage"]["row_group_rows"]
    made = gen_sales(n, seed, missing, group_rows) \
        if {"orders", "lineitem"} & set(missing) else {}
    for name in missing:
        table = made[name] if name in made \
            else gen_table(name, n, seed, group_rows)
        columns.write_parquet(table, paths[name], config["storage"])
    return paths, n
