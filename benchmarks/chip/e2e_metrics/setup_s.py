"""Process start to the start of the window: imports, weights made on
the device, programs compiled or read from the cache, warm-up, probes."""


def read(record):
    return record["setup_s"]
