"""Device: seconds in the backend's compile call for the serving
programs first used in set-up: the compile cache's read and the
executable's deserialize and load on a hit, XLA's compile on a miss."""

import setup_account


def read(record):
    return setup_account.rows_s(record, "load_ms")
