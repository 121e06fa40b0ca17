"""The host's own critical path per step: the time between two batch
pulls that the loop did *not* spend blocked on the device. The program's
exact histograms ``train.step_seconds`` (pull to pull) and
``train.loss_read_seconds`` (host time blocked in ``train.loss_read``,
with the next step already queued), (sum - sum) / count over the whole
run. While it is far under ``train_step.device_ms`` the chip sets the
pace; the day it is not, the host does again. None when telemetry is off
or the program has no ``train.loss_read_seconds`` (the parent of the PR
that keeps a step in flight). Layer: train loop. Moves ``tokens_per_s``."""


def read(ctx):
  from lddl_tpu.telemetry import get_telemetry
  tele = get_telemetry()
  steps = tele.histogram('train.step_seconds')
  reads = tele.histogram('train.loss_read_seconds')
  if not getattr(reads, 'count', 0) or not getattr(steps, 'count', 0):
    return None
  return 1e3 * (steps.sum - reads.sum) / steps.count
