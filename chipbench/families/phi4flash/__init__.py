"""The family ``phi4flash`` (SambaY): Mamba scans, sliding-window and full
differential attention, then gated memory units that read one scan's output
and cross-attention over one layer's keys and values, over a stream of token
sequences, as one chip of a data-parallel deployment holds them: whole
layers of one pipeline stage, a slice of the tied vocabulary.

``counts``     parameters, resident state, FLOPs a sequence and the kernels'
               operations and bytes, from the configuration's sizes alone;
``reference``  the plain float32 reference of this chip's share, its float8
               control, the weights from ``--seed``, and how the files' rows
               become its batch;
``program``    the one place that imports the program's model.

The first two import nothing of the program.

One dependency on a sister family, until a benchmark PR lifts the shared
parts into a module of ``chipbench`` itself: ``reference`` takes the lean
``follow`` (gradients and Adam a leaf at a time, so that the float32 state
fits beside one sequence) and the control's float8 rounding (``_fake_fp8``)
from ``families/laguna/reference.py``, and ``program.Side``'s ``tree`` /
``flat`` / ``inputs`` repeat the sisters'. An edit to Laguna's reference
therefore changes this cell's reference and control too:
``tests/chipbench/test_phi4flash_family.py`` and ``tests/test_phi4flash.py``
(the program against this reference, the control outside the limits) are
what would notice.
"""
