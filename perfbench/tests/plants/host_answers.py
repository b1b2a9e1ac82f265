"""Every device dispatch raises, so the breaker opens and the host
oracle answers in the device's place."""
from nomad_tpu.faultinject import faults

faults.arm("solver.dispatch", "error", count=10 ** 9)
