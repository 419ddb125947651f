"""durability: fsyncs at the durability point (count of the
``logdb.fsync_us`` histogram, all hosts) in the window, per thousand
acknowledged writes."""

KEY = "logdb.fsync_us.count"


def read(run):
    if run.acked_writes <= 0 or KEY not in run.registry_after:
        return None
    fsyncs = run.registry_after[KEY] - run.registry_before.get(KEY, 0)
    return 1000.0 * fsyncs / run.acked_writes
