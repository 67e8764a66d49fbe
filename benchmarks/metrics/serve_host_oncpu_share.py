"""Thread CPU time over wall time of the serve path's CPU-twinned brackets
that contain no other bracket (``launch`` holds ``stage1_tokenize`` and
``stage1_dispatch``, so it is left out): the rest of the wall time the thread
was blocked: the GIL, a lock, the device.  The program samples the CPU twin
(a costly clock), so each stage enters as its mean CPU per sampled bracket
times the brackets it ran, over the wall time of all of them."""

STAGES = (
    "stage1_tokenize", "stage1_dispatch", "stage1_postprocess",
    "stage2_gather", "stage2_packrows", "stage2_dispatch", "stage2_postprocess",
)


def read(ctx):
    cpu = wall = 0.0
    for stage in STAGES:
        n_cpu, cpu_s = ctx["hist"]("pathway_serve_stage_cpu_seconds", stage=stage)
        n_wall, wall_s = ctx["hist"]("pathway_serve_stage_seconds", stage=stage)
        if n_cpu and n_wall:
            cpu += cpu_s / n_cpu * n_wall
            wall += wall_s
    if wall <= 0:
        return None
    return 100.0 * cpu / wall
