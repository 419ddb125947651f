"""engine step loop: mean a round of the three passes over every lane an engine
holds, whatever the round carries (``engine_round_part_us.sum``, parts added):
``stage.reset`` (the zero-fill of the staging buffer), ``stage.tick`` (the tick
write over the live lanes, in a tick round) and ``upload.applied`` (the
``np.maximum`` of the applied cursors): work priced by lanes held, not busy."""

from benchmark.layer_metrics.finish_apply_ms import part_ms

PARTS = ("stage.reset", "stage.tick", "upload.applied")


def read(run):
    means = [part_ms(run, p) for p in PARTS]
    return None if None in means else sum(means)
