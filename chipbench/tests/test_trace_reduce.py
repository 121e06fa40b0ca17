"""The reduction from trace events to numbers: a hand-made trace with
hand-worked answers, and one recorded step pair of the s=128 cell."""

import gzip
import json
import os

import pytest

from chipbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
KERNEL = tr.KERNEL


def hand_made():
  ops = [
      ['w', 'while', 100, 100],      # a container: spans its body, left out
      ['a', 'fusion', 100, 40],      # [100, 140)
      ['k', KERNEL, 150, 30],        # [150, 180)
      ['b', 'fusion', 170, 20],      # [170, 190), overlaps k
      ['z', 'custom-call:AllocateBuffer', 190, 0],
      ['a', 'fusion', 250, 50],      # [250, 300)
      ['k', KERNEL, 310, 30],        # [310, 340)
      ['early', 'fusion', 10, 50],   # before the first step program
  ]
  modules = [['jit_step(1)', 100, 100], ['jit_step(1)', 250, 100],
             ['jit_init(2)', 0, 90]]
  host = [['chipbench.step_fn', 95, 110], ['chipbench.loader_next', 205, 35],
          ['chipbench.step_fn', 245, 110]]
  return {'devices': [{'plane': '/device:TPU:0', 'ops': ops,
                       'modules': modules}], 'host': host}


def test_hand_made_trace():
  out = tr.reduce(hand_made())
  d = out['devices'][0]
  assert d['window_ns'] == 250              # 100 .. 350, whole step programs
  assert d['steps'] == 2
  assert d['step_busy_ns'] == [80, 80]      # 40 + union(30, 20 -> 40); 50 + 30
  assert d['step_ns'] == [100, 100]         # the programs' own lengths
  assert d['busy_ns'] == 160
  assert d['step_gap_ns'] == [50]           # 200 .. 250
  assert d['kernel_ns'] == 60 and d['kernel_names'] == ['k']
  assert d['device_ops'][:3] == [['a', 90 / 1e9], ['k', 60 / 1e9],
                                 ['b', 20 / 1e9]]
  assert out['busy_s'] == 160 / 1e9 and out['window_s'] == 250 / 1e9
  gaps = out['breakdown']['idle_gaps']
  # 190..250 (60): the loader covers 35 of it; 140..150 and 300..310 lie
  # inside a step call; 340..350 is the tail of the last program.
  assert gaps[0] == ['between step calls, loader producing', 60 / 1e9]
  assert sorted(g[1] for g in gaps[1:]) == [10 / 1e9] * 3
  assert [g[0] for g in gaps[1:]] == ['inside a step call'] * 3


def test_mfu_is_read_from_the_traced_steps_on_the_device_clock():
  from chipbench import families, required_work, run
  config = run.load_json(os.path.join(os.path.dirname(HERE), 'configs',
                                      'bert-tiny.json'))
  steps = [{'rows': [40, 64], 'units': [40, 64], 'masked': [6, 9]},
           {'rows': [128, 100], 'units': [128, 100], 'masked': [19, 15]}]
  ctx = {'trace': tr.reduce(hand_made()), 'peaks': {'flops_per_s': 1e15},
         'chips': 1, 'config': config, 'train': {'max_predictions': 20},
         'family': families.load(config),
         'traced_steps': steps,
         # a slow loop around the same steps changes nothing:
         'wall_s': 1e9, 'steps': steps * 50}
  flops = sum(required_work.step_required_flops(
      config, s['rows'], s['units'], s['masked'], 20) for s in steps)
  got = run.read_per_layer(['train_step_mfu'], ctx)['train_step_mfu']
  assert got == pytest.approx(100.0 * flops / (200e-9 * 1e15))
  # The trace has to hold exactly the steps it was armed for.
  assert run.read_per_layer(['train_step_mfu'],
                            dict(ctx, traced_steps=steps[:1])) == {}
  assert run.read_per_layer(['train_step_mfu'], dict(ctx, trace=None)) == {}


def test_union():
  assert tr.union_ns([(0, 10), (5, 12), (20, 30), (22, 25)]) == 22
  assert tr.union_ns([]) == 0


def test_split_hlo():
  text = ('%attention.29 = (bf16[24,2048,64]{2,1,0:T(8,128)(2,1)}, '
          'bf16[24,2048,64]{2,1,0:T(8,128)(2,1)}) custom-call(bf16[24,2048,64]'
          '{2,1,0} %x, f32[2,1,2048]{2,1,0} %y), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
  assert tr.split_hlo(text) == ('attention.29', KERNEL)
  assert tr.split_hlo(
      '%custom-call.24 = bf16[12,768,768]{1,2,0:T(8,128)(2,1)} custom-call(), '
      'custom_call_target="AllocateBuffer"') == (
          'custom-call.24', 'custom-call:AllocateBuffer')
  assert tr.split_hlo(
      '%while.12 = (s32[]{:T(128)}, bf16[2,2048,768]{2,1,0}) while((s32[], '
      'bf16[2,2048,768]) %tuple.1), condition=%c, body=%b') == (
          'while.12', 'while')
  assert tr.split_hlo('%fusion.5 = f32[8]{0} fusion(f32[8]{0} %p), '
                      'kind=kLoop') == ('fusion.5', 'fusion')


def test_no_step_program_gives_nothing():
  events = hand_made()
  events['devices'][0]['modules'] = [['jit_init(2)', 0, 90]]
  assert tr.reduce(events) is None
  assert tr.reduce({'devices': [], 'host': []}) is None


def test_recorded_steps_of_the_s128_cell():
  path = os.path.join(HERE, 'fixtures', 'pairs_s128_two_steps.json.gz')
  if not os.path.exists(path):
    pytest.skip('no recorded trace in this tree')
  with gzip.open(path, 'rt') as f:
    recorded = json.load(f)
  out = tr.reduce(recorded['events'])
  d = out['devices'][0]
  want = recorded['expected']
  assert d['steps'] == want['steps']
  assert d['busy_ns'] == want['busy_ns']
  assert d['window_ns'] == want['window_ns']
  assert d['step_gap_ns'] == want['step_gap_ns']
  assert d['kernel_ns'] == want['kernel_ns'] == 0     # dense: no Mosaic kernel
  # What the numbers have to obey whatever the program becomes.
  assert 0 < d['busy_ns'] <= d['window_ns']
  assert sum(d['step_busy_ns']) == d['busy_ns']
  assert d['busy_ns'] + sum(d['step_gap_ns']) <= d['window_ns']
  assert len(out['breakdown']['device_ops']) == 10
