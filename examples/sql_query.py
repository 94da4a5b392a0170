#!/usr/bin/env python
"""Direct-SQL demo CLI: PG-Strom-style scans on TPU, end to end.

    SELECT k, COUNT(v), SUM(v), MEAN(v) FROM t [WHERE lo<=w<=hi] GROUP BY k
    SELECT city, AGG(v)  FROM t GROUP BY city          (string keys)
    SELECT d.attr, SUM(f.v) FROM fact JOIN dim ... GROUP BY d.attr LIMIT n
    SELECT v, k FROM t ORDER BY v DESC LIMIT n      (stats-eliminated scan)

Points at an existing Parquet file (--table) or synthesizes one
(--rows).  Column payloads ride the O_DIRECT engine and decode ON
DEVICE (sql/pq_direct.py: PLAIN bitcast, dictionary gather with the
on-device bit-unpack, compressed chunks direct); the aggregate runs on
device; per-query engine counters print after each query — on an
accelerator the uncompressed scan shows bounce_bytes == 0.

    python examples/sql_query.py --rows 2000000
    python examples/sql_query.py --table t.parquet --key k --value v
    python examples/sql_query.py --rows 500000 --compression zstd
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _synthesize(path: str, rows: int, groups: int,
                compression: str) -> None:
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    rng = np.random.default_rng(0)
    cities = np.array(["tokyo", "osaka", "kyoto", "nagoya", "sapporo",
                       "fukuoka", "sendai", "kobe"])
    tbl = pa.table({
        "k": pa.array(rng.integers(0, groups, rows, dtype=np.int32)),
        "v": pa.array(rng.standard_normal(rows, dtype=np.float32)),
        "w": pa.array(rng.integers(0, 10_000, rows, dtype=np.int32)),
        "city": pa.array(cities[rng.integers(0, len(cities), rows)]),
    })
    pq.write_table(tbl, path, row_group_size=max(4096, rows // 16),
                   compression=compression, use_dictionary=["city"])
    print(f"synthesized {rows} rows -> {path} "
          f"({os.path.getsize(path) >> 20} MiB, {compression})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--table", default=None,
                    help="existing Parquet file (else synthesized)")
    ap.add_argument("--sql", default=None, metavar="QUERY",
                    help="run this SQL string (table name 't') instead "
                         "of the demo queries — the sql.parser front "
                         "end plans it onto the device executors")
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--groups", type=int, default=64)
    ap.add_argument("--compression", default="none",
                    choices=("none", "zstd", "snappy", "gzip"))
    ap.add_argument("--key", default="k")
    ap.add_argument("--value", default="v")
    ap.add_argument("--top", type=int, default=5,
                    help="LIMIT for the ORDER BY demo query")
    ap.add_argument("--where", nargs=3, metavar=("COL", "LO", "HI"),
                    default=None,
                    help="range predicate; row groups the footer stats "
                         "exclude never leave the SSD")
    args = ap.parse_args(argv)

    from nvme_strom_tpu.utils.compile_cache import enable_compile_cache
    from nvme_strom_tpu.utils.device import device_line
    print(device_line(), flush=True)
    enable_compile_cache()
    from nvme_strom_tpu.io import StromEngine
    from nvme_strom_tpu.sql import (ParquetScanner, sql_groupby,
                                    sql_groupby_str, sql_topk,
                                    top_k_groups)

    path = args.table
    if path is None:
        import atexit
        import shutil
        tmp = tempfile.mkdtemp(prefix="strom_sql_")
        # one cleanup for every exit path — early returns, exceptions
        atexit.register(shutil.rmtree, tmp, ignore_errors=True)
        path = os.path.join(tmp, "t.parquet")
        _synthesize(path, args.rows, args.groups, args.compression)

    with StromEngine() as eng:
        sc = ParquetScanner(path, eng)
        print(f"table: {sc.num_rows} rows, "
              f"{sc.num_row_groups} row groups; direct eligibility: "
              f"{sc.direct_reasons([args.key, args.value])}")

        def counters(label: str, t0: float) -> None:
            eng.sync_stats()
            s = eng.stats.snapshot()
            print(f"  [{label}: {time.monotonic() - t0:.3f}s  "
                  f"direct={s['bytes_direct'] >> 20}MiB "
                  f"bounce={s['bounce_bytes'] >> 20}MiB]")

        if args.sql:
            from nvme_strom_tpu.sql import sql_query as run_sql
            t0 = time.monotonic()
            out = run_sql(args.sql, {"t": sc}, engine=eng)
            for name, col in out.items():
                if not hasattr(col, "__len__"):
                    print(f"  {name}: {col}")
                    continue
                def _fmt(x):
                    try:
                        return round(float(x), 4)
                    except (TypeError, ValueError):
                        return x
                head = [_fmt(x) for x in list(col[:8])]
                print(f"  {name}: {head}"
                      + (" ..." if len(col) > 8 else ""))
            counters("sql", t0)
            return 0

        where_ranges = []
        if args.where:
            col, lo, hi = args.where
            where_ranges = [(col, float(lo), float(hi))]

        t0 = time.monotonic()
        out = sql_groupby(sc, args.key, args.value, args.groups,
                          aggs=("count", "sum", "mean"),
                          where_ranges=where_ranges)
        head = {a: [round(float(x), 3) for x in list(out[a][:5])]
                for a in out}
        print(f"GROUP BY {args.key} (first 5 groups): {head}")
        counters("groupby", t0)

        t0 = time.monotonic()
        tk = sql_topk(sc, args.value, columns=[args.key], k=args.top,
                      where_ranges=where_ranges)
        print(f"ORDER BY {args.value} DESC LIMIT {args.top}: "
              f"{[round(float(x), 4) for x in tk[args.value]]} "
              f"(rows {list(tk['_row'])}, "
              f"{tk['_skipped_row_groups']} row groups eliminated)")
        counters("order by / limit", t0)

        if args.table is None:       # the synthesized string column
            t0 = time.monotonic()
            s_out = sql_groupby_str(sc, "city", args.value,
                                    aggs=("count", "mean"))
            top = top_k_groups(
                {k: v for k, v in s_out.items() if k != "labels"},
                "count", 3)
            print("GROUP BY city, top-3 by count:")
            for i in range(3):
                lab = s_out["labels"][int(top["group"][i])]
                lab = lab.decode() if isinstance(lab, bytes) else lab
                print(f"  {lab:<10} count={int(top['count'][i])} "
                      f"mean={float(top['mean'][i]):+.4f}")
            counters("string groupby", t0)

    return 0


if __name__ == "__main__":
    sys.exit(main())
