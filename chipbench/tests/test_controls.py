"""The control and the planted faults, at a size a test run can hold.

``controls.py`` as it is run on the chip, here on the rehearsal cell (the
tiny preset on the CPU, hidden dropout on): every reading goes through
``compare.judge`` with that cell's limits; the program comes out correct,
and the fp8 control, the half-batch fault and the unchanged state each
come out not correct.
"""

import json


def test_control_and_faults_are_not_correct(tmp_path, capsys):
  from chipbench import controls
  sides = controls.main(['--workload', 'rehearsal.pairs', '--seeds', '1,2',
                         '--control-seeds', '2', '--out', str(tmp_path)])
  capsys.readouterr()
  assert sides['program'] == [True, True]
  for side in ('control_fp8', 'fault_half_batch', 'fault_state_unchanged'):
    assert sides[side] == [False, False], side
  # Which number catches what: the control by the median leaf's change,
  # the half batch by the median leaf's gradient, the unchanged state by
  # a change of nought (a gap of 1).
  rows = json.loads((tmp_path / 'rehearsal.pairs.json').read_text())
  for row in rows:
    over = row['verdict']['over']
    if row['side'] == 'control_fp8':
      assert 'change_gap_median' in over
    elif row['side'] == 'fault_half_batch':
      assert 'grad_gap_median' in over
    elif row['side'] == 'fault_state_unchanged':
      assert row['change_gap'] == 1.0 and 'change_gap' in over
  # Read again under the limits file as it is now: the same verdicts.
  again = controls.main(['--workload', 'rehearsal.pairs', '--rejudge',
                         str(tmp_path / 'rehearsal.pairs.json')])
  assert again == sides
