"""transport: mean a round of both sends of ``resolve``
(``engine_round_part_us.sum{part=resolve.send}``): the REPLICATEs before the
save and everything else after it, as the sending engine pays them: one batch a
target host, the receivers' registries, ``_dirty_mu`` and ``node.mu``.  The
mesh cell is not listed: resident links send nothing this way."""

from benchmark.layer_metrics.finish_apply_ms import part_ms


def read(run):
    return part_ms(run, "resolve.send")
