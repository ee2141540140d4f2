"""Share of the training window the host spent waiting in the program's
BatchFeed.get() (the benchmark's own timer around each call)."""


def read(run):
    r = run.records
    return 100.0 * r["data_wait_s"] / r["window_s"]
