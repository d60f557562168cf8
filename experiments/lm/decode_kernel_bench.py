"""Time ``flash_decode_paged`` alone, at the serving cell's shape.

    python experiments/lm/decode_kernel_bench.py [--tree DIR] [--out FILE]

One process, one chip. The shape is ``serve-rate-mixed``'s (``PERF.md`` §4):
32 rows, tables 16 wide, pages of 128, packed width 2048 (16 heads of 128),
bf16, a pool of 128 pages. Each case is a page table and a vector of lengths
(among them 1, 3, 5, 12 and 32 live rows at the cell's context lengths beside
retired rows); the kernel runs ``CALLS`` times in one ``lax.scan`` (each call's query is the
last call's output, so none is folded away) and the figure is microseconds a
call: the host's clock around ``block_until_ready`` and, beside it, the
kernel's own events in a profiler trace of one more scan. ``--tree`` puts
another checkout's ``distriflow_tpu`` first on the path (the parent commit's,
to compare on the same chip). Refuses to run without a TPU: a time from the
CPU is not a number.
"""
import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
B, WIDTH, PAGE, H, D, N_PAGES = 32, 16, 128, 16, 128, 128
CALLS, REPEATS = 96, 7
# thirteen live rows as the cell keeps them at 4.6 requests/s: contexts
# of 150-1,900 tokens, mean about 600 (PERF.md §5)
LIVE_LENGTHS = (200, 450, 700, 1600, 300, 520, 900, 150, 640, 410, 1000, 260, 680)
STALE_LENGTH = 1900  # a retired slot's cache_index goes on growing


def cases(np):
    """name -> (table [B, WIDTH] int32, lengths [B] int32, what it shows)."""
    sentinel = N_PAGES
    distinct = (np.arange(B * WIDTH, dtype=np.int32).reshape(B, WIDTH)
                % N_PAGES)  # no two consecutive tiles share a page
    full = np.full((B,), WIDTH * PAGE, np.int32)
    out = {"a_full": (distinct, full, "every tile live and fetched")}

    table = np.full((B, WIDTH), sentinel, np.int32)
    lengths = np.full((B,), STALE_LENGTH, np.int32)
    page = 0
    live_rows = [(5 * i) % B for i in range(len(LIVE_LENGTHS))]
    for row, n in zip(live_rows, LIVE_LENGTHS):
        reserved = min(-(-n // PAGE) + 1, WIDTH)  # one page not yet written
        table[row, :reserved] = np.arange(page, page + reserved)
        page += reserved
        lengths[row] = n
    assert page <= N_PAGES
    out["b_cell"] = (table, lengths,
                     "13 live rows of ~600, 19 all-sentinel rows, stale lengths")

    one = np.full((B, WIDTH), sentinel, np.int32)
    one[:, 0] = np.arange(B)
    out["c_len1"] = (one, np.ones((B,), np.int32), "every row at length 1")

    same = np.zeros((B, WIDTH), np.int32)
    out["d_full_one_page"] = (same, full,
                              "every tile live, none fetched: compute alone")
    out["e_all_dead"] = (np.full((B, WIDTH), sentinel, np.int32),
                         np.full((B,), STALE_LENGTH, np.int32),
                         "no live row: the bare grid")
    for n_live in (1, 3, 5, 12, 32):
        out[f"live_{n_live:02d}"] = live_case(np, n_live) + (
            f"{n_live} live rows of the cell's lengths, the rest retired",)
    return out


def live_case(np, n_live):
    """(table, lengths): ``n_live`` rows at the cell's context lengths, each
    with one reserved page not yet written, spread over the slots; the
    other rows retired (all sentinels, a stale length). Pages wrap round the
    pool when the rows need more than it holds: a row still reads one page
    a tile."""
    table = np.full((B, WIDTH), N_PAGES, np.int32)
    lengths = np.full((B,), STALE_LENGTH, np.int32)
    page = 0
    for i in range(n_live):
        row, n = (5 * i) % B, LIVE_LENGTHS[i % len(LIVE_LENGTHS)]
        reserved = min(-(-n // PAGE) + 1, WIDTH)
        table[row, :reserved] = (page + np.arange(reserved)) % N_PAGES
        page += reserved
        lengths[row] = n
    return table, lengths


def time_kernel(jax, jnp, np, xplane, fn, table, lengths, pools, q0):
    """Microseconds a call of ``fn(q, k_pool, v_pool, table, lengths)``:
    the host's median and least over ``REPEATS`` scans of ``CALLS`` calls,
    and the kernel's own events in a profiler trace of one more scan."""
    @jax.jit
    def many(q, k_pool, v_pool, table, lengths):
        def one(q, _):
            return fn(q, k_pool, v_pool, table, lengths), None
        return jax.lax.scan(one, q, None, length=CALLS)[0]

    operands = (q0, *pools, jnp.asarray(table), jnp.asarray(lengths))
    many(*operands).block_until_ready()  # compile, warm
    host = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        many(*operands).block_until_ready()
        host.append((time.perf_counter() - t0) / CALLS * 1e6)
    trace_dir = tempfile.mkdtemp(prefix="fd_bench_")
    try:
        with jax.profiler.trace(trace_dir):
            many(*operands).block_until_ready()
        red = xplane.reduce(xplane.load(xplane.find_xplane(trace_dir)))
        secs, calls = red.kernel_seconds("flash_decode_paged")
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    live_tiles = int(sum(min(-(-int(n) // PAGE), int((row < N_PAGES).sum()))
                         for row, n in zip(table, lengths)))
    return {"tiles": B * WIDTH, "live_tiles": live_tiles,
            "us_per_call_trace": round(secs / max(calls, 1) * 1e6, 2),
            "trace_calls": calls,
            "us_per_call_host_median": round(statistics.median(host), 2),
            "us_per_call_host_min": round(min(host), 2)}


def pools_and_query(jax, jnp):
    kk, kv, kq = jax.random.split(jax.random.PRNGKey(0), 3)
    k_pool = jax.random.normal(kk, (N_PAGES, PAGE, H * D), jnp.bfloat16)
    v_pool = jax.random.normal(kv, (N_PAGES, PAGE, H * D), jnp.bfloat16)
    return (k_pool, v_pool), jax.random.normal(kq, (B, H, D), jnp.bfloat16)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tree", default=ROOT,
                    help="checkout whose distriflow_tpu is timed")
    ap.add_argument("--out", default=None, help="also append the lines here")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))
    sys.path.insert(1, ROOT)  # benchmark.lib.xplane, the same reader for both

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import xplane
    from distriflow_tpu.ops.flash_decode import flash_decode_paged

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU here ({dev.platform}): nothing to time", file=sys.stderr)
        return 2

    pools, q0 = pools_and_query(jax, jnp)
    for name, (table, lengths, what) in cases(np).items():
        timed = time_kernel(jax, jnp, np, xplane, flash_decode_paged, table,
                            lengths, pools, q0)
        line = json.dumps({
            "case": name, "what": what, "tree": os.path.abspath(args.tree),
            "device_kind": dev.device_kind, **timed})
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
