"""The DPU hop: time the host spends in `ros2.dpu.call` (doorbell,
queue, completion wait) while no `ros2.dpu.exec` handler runs on a DPU
worker, in ms per GB landed in HBM in the traced window."""
from bench import program_spans


def read(run):
    ps = program_spans.of_run(run)
    if ps is None or not ps.has("ros2.dpu.call"):
        return None
    return program_spans.ms_per_GB(
        run, ps.program_minus(("ros2.dpu.call",), ("ros2.dpu.exec",)))
