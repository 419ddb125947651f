"""mesh engine: the least time the interconnect could take to bring one
chip what it receives in one step's exchange (``collective_bytes.py`` at
the configuration's own ``n_local``, over the chip-to-chip peak) as a
share of ``exchange_us_per_chip``.  At 48 rows a chip the exchange is 18
small collectives and latency bounds it; at 1,024 rows a chip it is 21
times the bytes, and the share says whether bandwidth has begun to."""

from benchmark import collective_bytes, layers, peaks


def read(run):
    per_chip_us = layers.load_reader("exchange_us_per_chip")(run)
    if per_chip_us is None:
        return None
    least_s = (collective_bytes.received_per_step(run.config)
               / (peaks.peaks_of(run.device_kind)["ici_bits_per_s"] / 8))
    return 100.0 * least_s * 1e6 / per_chip_us
