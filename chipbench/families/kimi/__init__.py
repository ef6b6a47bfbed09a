"""The family ``kimi``: multi-head latent attention (keys and values
through a low-rank latent, one rotary key part shared by every head) and a
sigmoid-routed mixture of experts with shared experts beside it, over a
stream of token sequences, as one chip of an expert-parallel deployment
holds them: some of each layer's routed experts, a slice of the
vocabulary, the layers of one pipeline stage.

``counts``     parameters, resident state, FLOPs a sequence and the kernels'
               operations and bytes, from the configuration's sizes alone;
``reference``  the plain float32 reference of this chip's share, its float8
               control, the weights from ``--seed``, and how the files' rows
               become its batch;
``program``    the one place that imports the program's model.

The first two import nothing of the program. ``reference`` takes the lean
``follow`` and the control's float8 rounding (``_fake_fp8``) from
``families/laguna/reference.py``, as ``families/keye`` and
``families/phi4flash`` do.
"""
