"""A start's seconds by where the program spent them, from its own
account (`utils/profiler.py`, the program account: `PhaseProfiler
.summary()["programs"]`, which a traced run keeps as
`record["phases"]["programs"]`): the batcher's build, and one row a
program first used, split by `jax.monitoring`'s events into trace,
lowering, load (the compile cache's read and deserialize on a hit, XLA's
compile on a miss) and the rest of the call, its first run. Only what
happened before the scheduler thread started is set-up (`serving`
false). A program without the account (an older batcher) gives None,
and the metric is left out."""


def account(record):
    """The program account of a traced run, or None."""
    acct = (record.get("phases") or {}).get("programs")
    return acct if isinstance(acct, dict) and "rows" in acct else None


def rows_s(record, *fields):
    """Seconds, summed over the admit, chunk and spec programs first
    used in set-up, of the rows' `fields` (milliseconds)."""
    acct = account(record)
    if acct is None:
        return None
    return sum(r[f] for r in acct["rows"] if not r["serving"]
               for f in fields) / 1e3


def outside_rows(acct):
    """What compiled in set-up outside the rows: the build's two parts
    and the programs of threads with no label (those of the build's own
    `eager` among them)."""
    build = acct["build"]
    return [build.get("weights", {}), build.get("pool", {}),
            acct["eager"]["setup"]]
