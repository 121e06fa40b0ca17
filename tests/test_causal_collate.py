"""The causal collate of packed rows (loader/packed.py): a decoder's
next-token batch whose labels, positions and document ids never cross a
document boundary."""

import numpy as np

from lddl_tpu.core.utils import serialize_np_array
from lddl_tpu.loader.packed import CausalPackedCollate


class _Tok:
  pad_token_id = 0


def _rows(specs):
  """Wire rows ``[CLS] p0 [SEP] p1 [SEP] ...`` as preprocess/packed.py
  writes them, each piece's first token marked."""
  rows = []
  for pieces in specs:
    ids, marks = [101], []
    for n in pieces:
      marks.append(len(ids))
      ids.extend(range(1000 + 100 * len(marks), 1000 + 100 * len(marks) + n))
      ids.append(102)
    rows.append({'input_ids': serialize_np_array(np.asarray(ids, np.uint16)),
                 'doc_offsets': serialize_np_array(np.asarray(marks,
                                                              np.uint16))})
  return rows


def test_labels_positions_and_documents():
  batch = CausalPackedCollate(_Tok())(_rows([[3, 2], [5]]), 16, epoch=0,
                                      step=0)
  assert sorted(batch) == ['input_ids', 'labels', 'positions', 'segment_ids']
  ids, seg = batch['input_ids'], batch['segment_ids']
  # Row 0: [CLS] 1100 1101 1102 [SEP] | 1200 1201 [SEP] | padding.
  np.testing.assert_array_equal(
      ids[0, :9], [101, 1100, 1101, 1102, 102, 1200, 1201, 102, 0])
  np.testing.assert_array_equal(seg[0], [0] * 5 + [1] * 3 + [-1] * 8)
  np.testing.assert_array_equal(batch['positions'][0, :8],
                                [0, 1, 2, 3, 4, 0, 1, 2])
  # The next id of the same document; the last token of a document and
  # padding are ignored.
  np.testing.assert_array_equal(
      batch['labels'][0],
      [1100, 1101, 1102, 102, -100, 1201, 102, -100] + [-100] * 8)
  np.testing.assert_array_equal(seg[1], [0] * 7 + [-1] * 9)
  np.testing.assert_array_equal(batch['labels'][1, :7],
                                [1100, 1101, 1102, 1103, 1104, 102, -100])
  assert all(v.dtype == np.int32 and v.shape == (2, 16)
             for v in batch.values())
