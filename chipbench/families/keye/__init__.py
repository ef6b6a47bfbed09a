"""The family ``keye``: grouped-query attention over the keys a learned
indexer chooses for each query (DeepSeek Sparse Attention: a lightning
indexer's scores, an exact top-k a query, the indexer trained by its own
loss beside the next-token loss) and a softmax-routed mixture of experts,
over a stream of token sequences, as one chip of an expert-parallel
deployment holds them: some of each layer's experts, a slice of the
vocabulary, the layers of one pipeline stage.

``counts``     parameters, resident state, FLOPs a sequence and the kernels'
               operations and bytes, from the configuration's sizes alone;
``reference``  the plain float32 reference of this chip's share, its float8
               control, the weights from ``--seed``, and how the files' rows
               become its batch;
``program``    the one place that imports the program's model.

The first two import nothing of the program. ``reference`` takes the lean
``follow`` and the control's float8 rounding (``_fake_fp8``) from
``families/laguna/reference.py``, as ``families/phi4flash`` does.
"""
