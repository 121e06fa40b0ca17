"""The capture summary's second stage (``telemetry/capture.py:summarize``):
a hand-made trace with hand-worked answers, and three recorded steps of
the ``bert-base.pairs-s128`` cell cut from a chip trace (PR 25's chip run,
TPU v5 lite, seed 250918: ``extract``'s output for the 13th to 15th of the
40 traced step programs, times shifted to start near nought).

What has to hold whatever the program becomes: the module classes sum to
the busy time inside step programs, so do the passes, and each gap's
phases plus ``unattributed`` sum to the gap.
"""

import gzip
import json
import os

import pytest

from lddl_tpu.telemetry import capture

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, 'fixtures',
                       'capture_pairs_s128_three_steps.json.gz')

_LAYER = 'encoder/while/body/closed_call/layers.body/layers/'
ATT = f'jit(step)/jvp(BertForPretraining)/{_LAYER}attention/query/dot_general'
FFN_B = (f'jit(step)/transpose(jvp(BertForPretraining))/{_LAYER}'
         'output/dot_general')
OPT = 'jit(step)/optimizer/add'
DROP = 'jit(step)/jvp(BertForPretraining)/embed_dropout/jit(_bernoulli)/lt'


def hand_made():
  names = [['w', 'while', ''],                  # 0: a container, left out
           ['a', 'convolution fusion', ATT],    # 1
           ['f', 'convolution fusion', FFN_B],  # 2
           ['o', 'loop fusion', OPT],           # 3
           ['d', 'loop fusion', DROP],          # 4
           ['c', 'copy-done', '']]              # 5: no op_name
  ops = [
      [0, 100, 100],  # the loop spans its body
      [1, 100, 40],   # attention  [100, 140)
      [2, 150, 30],   # ffn        [150, 180)
      [4, 170, 20],   # dropout    [170, 190): 10 of it not under ffn
      [3, 190, 10],   # optimizer  [190, 200)
      [1, 300, 50],   # attention  [300, 350)
      [5, 350, 0],    # no duration
      [5, 360, 30],   # unscoped   [360, 390)
      [3, 395, 20],   # optimizer  [395, 415): cut at the program's end, 400
      [1, 700, 100],  # attention  [700, 800)
      [1, 10, 50],    # before the first step program
      [3, 250, 10],   # between two step programs
  ]
  modules = [['jit_step(1)', 100, 100], ['jit_step(1)', 300, 100],
             ['jit_step(2)', 700, 100], ['jit_init(3)', 0, 90]]
  main = {'line': 'python', 'events': [
      ['train.step', 90, 160, 7],
      ['train.dispatch', 95, 10, 7],
      ['train.loss_read', 110, 100, 7],    # 200..210 of the gap 200..300
      ['train.after_step', 215, 30, 7],    # 215..245
      ['train.step', 255, 175, 8],
      ['train.data_wait', 260, 20, 8],     # 260..280
      ['train.dispatch', 285, 30, 8],      # 285..300 of it in the gap
      ['train.loss_read', 320, 90, 8],     # 400..410 of the gap 400..700
      ['train.after_step', 410, 20, 8],
      ['train.step', 430, 500, 9],
      ['train.data_wait', 430, 40, 9],
      ['train.epoch_turn', 470, 150, 9],
      ['train.data_wait', 620, 30, 9],
      ['train.dispatch', 660, 45, 9],      # 660..700 of it in the gap
  ]}
  feed = {'line': 'python', 'events': [
      ['loader.next', 200, 90, None], ['train.h2d', 290, 10, None]]}
  return {'devices': [{'plane': '/device:TPU:0', 'names': names, 'ops': ops,
                       'modules': modules}], 'host': [feed, main]}


def test_hand_made_classes_and_passes():
  out = capture.summarize(hand_made())
  (d,) = out['devices']
  assert d['steps'] == 3 and d['step_programs_ns'] == 300
  assert d['classes'] == dict.fromkeys(capture.CLASSES, 0) | {
      'attention': 40 + 50 + 100, 'ffn': 30, 'dropout': 10,
      'optimizer': 10 + 5, 'unscoped': 30}
  assert d['passes'] == {'forward': 190 + 10, 'backward': 30,
                         'recompute': 0, 'update': 15 + 30}
  assert d['busy_ns'] == 275 == sum(d['classes'].values())
  assert d['top_ops'][0] == ['a', 'attention', 'forward', 190]
  assert out['phases_seen'] == [
      'train.after_step', 'train.data_wait', 'train.dispatch',
      'train.epoch_turn', 'train.loss_read', 'train.step']


def test_hand_made_gaps_by_phase():
  (d,) = capture.summarize(hand_made())['devices']
  first, second = d['gaps']
  assert first == {
      'ns': 100, 'step': 8, 'epoch_turn': False, 'first_step': True,
      'phases': {'train.loss_read': 10, 'train.after_step': 30,
                 'train.data_wait': 20, 'train.dispatch': 15,
                 'unattributed': 25},
      'feed': {'loader.next': 90, 'train.h2d': 10}}
  assert second == {
      'ns': 300, 'step': 9, 'epoch_turn': True, 'first_step': False,
      'phases': {'train.loss_read': 10, 'train.after_step': 20,
                 'train.data_wait': 70, 'train.epoch_turn': 150,
                 'train.dispatch': 40, 'unattributed': 10},
      'feed': {}}
  assert d['device_clock_shift_ns'] is None  # no enqueue span in the trace
  table = capture.format_table(capture.summarize(hand_made()))
  assert 'longest: 0.000 ms before step 9 [epoch turn]' in table


def test_device_clock_is_moved_onto_the_hosts():
  # The capture's first step program is launched into an idle chip
  # (StepProfiler starts a trace only once the loop has drained), so it
  # starts as its own enqueue begins: the first enqueue from the start of
  # the first train.dispatch (95) on, at 98, against a program that
  # "starts" at 100. The device's times move by -2 and the gaps with them;
  # the later enqueues, 5 after and 1 before their programs, decide
  # nothing.
  events = hand_made()
  events['host'].append({'line': 'tfrt-non-blocking-queue', 'events': [
      ['DoEnqueueProgram', 60, 3, None],  # before the loop's first dispatch
      ['DoEnqueueProgram', 98, 3, None], ['DoEnqueueProgram', 305, 3, None],
      ['DoEnqueueProgram', 699, 3, None]]})
  (d,) = capture.summarize(events)['devices']
  assert d['device_clock_shift_ns'] == -2
  first, second = d['gaps']
  assert first['ns'] == 100 and second['ns'] == 300
  assert first['phases'] == {       # the gap is 198..298 on the host's clock
      'train.loss_read': 12, 'train.after_step': 30, 'train.data_wait': 20,
      'train.dispatch': 13, 'unattributed': 25}
  assert second['phases'] == {      # 398..698
      'train.loss_read': 12, 'train.after_step': 20, 'train.data_wait': 70,
      'train.epoch_turn': 150, 'train.dispatch': 38, 'unattributed': 10}
  assert d['busy_ns'] == 275        # the device's own sums do not move
  assert 'moved by -0.000 ms' in capture.format_table(
      dict(capture.summarize(events), seconds=0.0, trace_bytes=0))


def one_step_in_flight(lead=20):
  """A capture of ``TrainLoop.run`` as it is since PR 26: four step
  programs (steps 11-14), the first launched into an idle chip, each of
  the others queued before the one before it ends. Host times below; the
  device's raw clock reads ``lead`` less."""
  programs = [(1050, 1150), (1150, 1250), (1250, 1350), (1355, 1450)]
  modules = [['jit_step(1)', lo - lead, hi - lo] for lo, hi in programs]
  names = [['a', 'convolution fusion', ATT]]
  ops = [[0, lo - lead, hi - lo] for lo, hi in programs]
  main = {'line': 'python', 'events': [
      ['train.step', 1030, 31, 11],
      ['train.data_wait', 1030, 5, 11],
      ['train.dispatch', 1040, 20, 11],     # nothing in flight: no loss read
      ['train.step', 1062, 98, 12],
      ['train.data_wait', 1062, 3, 12],
      ['train.dispatch', 1066, 14, 12],
      ['train.loss_read', 1082, 70, 11],    # program 11 ends at 1150
      ['train.after_step', 1153, 5, 11],
      ['train.step', 1160, 102, 13],
      ['train.data_wait', 1160, 3, 13],
      ['train.dispatch', 1164, 11, 13],
      ['train.loss_read', 1176, 77, 12],    # program 12 ends at 1250
      ['train.after_step', 1254, 6, 12],
      ['train.step', 1262, 100, 14],
      ['train.data_wait', 1262, 3, 14],
      ['train.dispatch', 1266, 14, 14],
      ['train.loss_read', 1282, 70, 13],    # program 13 ends at 1350
      ['train.after_step', 1353, 7, 13],
  ]}
  queue = {'line': 'tfrt-non-blocking-queue', 'events': [
      ['DoEnqueueProgram', t, 2, None] for t in (1050, 1070, 1168, 1270)]}
  return {'devices': [{'plane': '/device:TPU:0', 'names': names, 'ops': ops,
                       'modules': modules}], 'host': [main, queue]}


@pytest.mark.parametrize('lead', [20, 0, -7, 1500])
def test_clock_shift_with_a_step_in_flight_is_the_anchored_one(lead):
  # With lead 20, the enqueue nearest to the third program's raw start
  # (1230) is the *next* step's (1270): the old rule, the largest lead
  # over the nearest enqueue, read 40. The anchor reads the lead itself.
  (d,) = capture.summarize(one_step_in_flight(lead))['devices']
  assert d['device_clock_shift_ns'] == lead
  assert d['steps'] == 4 and d['busy_ns'] == 395


def test_back_to_back_programs_still_give_one_gap_each():
  (d,) = capture.summarize(one_step_in_flight())['devices']
  gaps = d['gaps']
  assert [g['ns'] for g in gaps] == [0, 0, 5]
  assert [g['step'] for g in gaps] == [12, 13, 14]
  assert gaps[0]['phases'] == gaps[1]['phases'] == {'unattributed': 0}
  # 1350..1355 on the host's clock: the wake-up from the loss read, then
  # the observers of step 13.
  assert gaps[2]['phases'] == {'train.loss_read': 2, 'train.after_step': 2,
                               'unattributed': 1}
  for g in gaps:
    assert sum(g['phases'].values()) == g['ns']
    assert not g['epoch_turn']
  assert [g['first_step'] for g in gaps] == [False, False, False]
  table = capture.format_table(
      dict(capture.summarize(one_step_in_flight()), seconds=0.0,
           trace_bytes=0))
  assert 'gaps between step programs: 3, mean 0.000 ms' in table


def test_programs_that_overlap_by_rounding_give_a_gap_of_nought():
  events = one_step_in_flight()
  events['devices'][0]['modules'][1][1] -= 1  # starts (and ends) 1 ns early
  (d,) = capture.summarize(events)['devices']
  assert [g['ns'] for g in d['gaps']] == [0, 1, 5]
  assert all(sum(g['phases'].values()) == g['ns'] for g in d['gaps'])


def with_collections(events, main_pauses=(), feed_pauses=()):
  """``events`` with ``host.gc`` spans (``[start, duration]``) on the main
  and the feed's thread lines."""
  feed, main = events['host']
  main['events'] += [['host.gc', s, d, None] for s, d in main_pauses]
  feed['events'] += [['host.gc', s, d, None] for s, d in feed_pauses]
  return events


def test_a_collection_is_laid_over_the_gaps_and_is_no_phase():
  # On the feed's thread, 20 of the first gap (200..300) and 10 of the
  # second (400..700); on the main thread, 5 under train.after_step.
  events = with_collections(hand_made(), main_pauses=[(220, 5)],
                            feed_pauses=[(205, 20), (390, 20)])
  out = capture.summarize(events)
  plain = capture.summarize(hand_made())
  assert out['phases_seen'] == plain['phases_seen']
  (d,), (p,) = out['devices'], plain['devices']
  assert [g['gc'] for g in d['gaps']] == [25, 10]
  for gap, before in zip(d['gaps'], p['gaps']):
    assert {k: v for k, v in gap.items() if k != 'gc'} == before
  table = capture.format_table(dict(out, seconds=0.0, trace_bytes=0))
  assert ("the feed's thread meanwhile: no span; the collector: 0.000"
          in table)


def test_a_trace_without_collections_gives_no_gc_field():
  (d,) = capture.summarize(hand_made())['devices']
  assert all('gc' not in g for g in d['gaps'])
  assert 'collector' not in capture.format_table(
      dict(capture.summarize(hand_made()), seconds=0.0, trace_bytes=0))


def test_no_step_program_or_no_phases():
  events = hand_made()
  events['host'] = []
  (d,) = capture.summarize(events)['devices']
  assert [g['phases'] for g in d['gaps']] == [{'unattributed': 100},
                                              {'unattributed': 300}]
  assert [g['step'] for g in d['gaps']] == [None, None]
  events['devices'][0]['modules'] = [['jit_init(3)', 0, 90]]
  assert capture.summarize(events) == {'devices': [], 'phases_seen': []}
  assert 'no step program' in capture.format_table(
      dict(capture.summarize(events), seconds=0.0, trace_bytes=0))


@pytest.fixture(scope='module')
def recorded():
  with gzip.open(FIXTURE, 'rt') as f:
    return json.load(f)


@pytest.fixture(scope='module')
def recorded_device(recorded):
  (d,) = capture.summarize(recorded['events'])['devices']
  return d


def test_recorded_steps_totals(recorded, recorded_device):
  d, want = recorded_device, recorded['expected']
  assert d['steps'] == want['steps'] == 3
  assert d['busy_ns'] == want['busy_ns']
  assert 0.9 * d['step_programs_ns'] < d['busy_ns'] <= d['step_programs_ns']
  assert sum(d['classes'].values()) == d['busy_ns']
  assert sum(d['passes'].values()) == d['busy_ns']
  assert [g['ns'] for g in d['gaps']] == want['gap_ns']
  assert [g['step'] for g in d['gaps']] == want['gap_steps']
  # Every step program of the capture "started" ~1.5 ms before the host
  # had enqueued it: the device's clock is moved onto the host's, by the
  # lead of the first of the three (recorded under the serial loop, where
  # every program was launched into an idle chip).
  assert d['device_clock_shift_ns'] == want['device_clock_shift_ns']
  assert 1_000_000 < d['device_clock_shift_ns'] < 2_000_000


@pytest.mark.parametrize('module_class', capture.CLASSES)
def test_recorded_steps_by_class(recorded, recorded_device, module_class):
  got = recorded_device['classes'][module_class]
  assert got == recorded['expected']['classes'][module_class]
  # base s128 trains with dropout and without remat: every class has
  # device time, and less than all of it.
  assert 0 < got < recorded_device['busy_ns']


@pytest.mark.parametrize('pass_', capture.PASSES)
def test_recorded_steps_by_pass(recorded, recorded_device, pass_):
  got = recorded_device['passes'][pass_]
  assert got == recorded['expected']['passes'][pass_]
  assert (got == 0) == (pass_ == 'recompute')  # the cell runs without remat


@pytest.mark.parametrize('gap', [0, 1])
def test_recorded_gaps_split_over_the_phases(recorded_device, gap):
  g = recorded_device['gaps'][gap]
  assert sum(g['phases'].values()) == g['ns']
  assert g['phases']['unattributed'] >= 0
  for phase in ('train.loss_read', 'train.after_step', 'train.data_wait',
                'train.dispatch'):
    assert 0 < g['phases'][phase] < g['ns'], phase
  assert not g['epoch_turn'] and not g['first_step']
