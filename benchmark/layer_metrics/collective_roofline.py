"""mesh engine: the least time the interconnect could take to bring one
chip what it receives in one step's exchange (``collective_bytes.py`` over
the chip-to-chip peak) as a share of the collectives' measured device time
per step and chip.  The exchange is many small collectives, so latency
bounds it, not bandwidth; the share says by how far."""

from benchmark import collective_bytes, peaks


def read(run):
    prog = run.step_program()
    if prog is None or prog["calls"] <= 0 or "mesh" not in run.config:
        return None
    if run.capture["collective_s"] <= 0:
        return None
    least_s = (collective_bytes.received_per_step(run.config)
               / (peaks.peaks_of(run.device_kind)["ici_bits_per_s"] / 8))
    # one call of the step program per chip and step
    return 100.0 * least_s / (run.capture["collective_s"] / prog["calls"])
