"""mesh engine: messages of the mesh engine's replicas that met the host
transport per engine step: the window's growth of ``engine_mesh_hub_msgs``
over every ``way`` (sent over cut or off-mesh links, reads forwarded host
to host, stray hub copies dropped at the gate) over the step entry's calls.
0 where the fabric carried everything."""

from benchmark.window_registry import delta_over_labels, ratio


def read(run):
    return ratio(delta_over_labels(run, "engine_mesh_hub_msgs"),
                 run.window_step_calls())
