"""kernel: of the lanes the engines held while the window ran, the share the
kernel's quiesce mask had asleep, in percent.  At every fleet digest (each
tenth round) an engine adds the digest's two counts to
``engine_fleet_lanes{what=occupied|quiesced}``; this is the window's growth
of the one over the other's.  90.6 in ``fleet-1k.write16-hot96`` while all
2,784 idle replicas of 3,072 sleep; 0.0 where no group may quiesce."""

from benchmark.window_registry import delta, key, ratio


def read(run):
    return ratio(delta(run, key("engine_fleet_lanes", what="quiesced")),
                 delta(run, key("engine_fleet_lanes", what="occupied")),
                 100.0)
