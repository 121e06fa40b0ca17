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
  # The runtime's enqueue spans begin 2 before, 5 after and 1 before the
  # three step programs "start": no program runs before it is enqueued, so
  # the device's times move by +5 and the gaps with them.
  events = hand_made()
  events['host'].append({'line': 'tfrt-non-blocking-queue', 'events': [
      ['DoEnqueueProgram', 98, 3, None], ['DoEnqueueProgram', 305, 3, None],
      ['DoEnqueueProgram', 699, 3, None]]})
  (d,) = capture.summarize(events)['devices']
  assert d['device_clock_shift_ns'] == 5
  first, second = d['gaps']
  assert first['ns'] == 100 and second['ns'] == 300
  assert first['phases'] == {       # the gap is 205..305 on the host's clock
      'train.loss_read': 5, 'train.after_step': 30, 'train.data_wait': 20,
      'train.dispatch': 20, 'unattributed': 25}
  assert second['phases'] == {      # 405..705
      'train.loss_read': 5, 'train.after_step': 20, 'train.data_wait': 70,
      'train.epoch_turn': 150, 'train.dispatch': 45, 'unattributed': 10}
  assert d['busy_ns'] == 275        # the device's own sums do not move
  assert 'moved by +0.000 ms' in capture.format_table(
      dict(capture.summarize(events), seconds=0.0, trace_bytes=0))


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
  # had enqueued it: the device's clock is moved onto the host's.
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
