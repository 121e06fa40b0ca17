"""Mean time of an epoch turn, from closing the exhausted device feed (the
join of its prefetch thread) to the first batch of the next epoch in
hand: the program's exact ``train.epoch_turn_seconds`` histogram, sum over
count, over the whole run (set-up steps included: a turn is rare). None
when telemetry is off or no epoch turned. Layer: loader. Moves
``tokens_per_s``."""


def read(ctx):
  from lddl_tpu.telemetry import get_telemetry
  turns = get_telemetry().histogram('train.epoch_turn_seconds')
  if not getattr(turns, 'count', 0):
    return None
  return 1e3 * turns.sum / turns.count
