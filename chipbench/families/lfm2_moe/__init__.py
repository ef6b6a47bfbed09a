"""The family ``lfm2_moe``: gated short convolutions, grouped-query causal
attention and a sparse mixture of experts over a stream of token sequences,
as one chip of an expert-parallel deployment holds them: some of each
layer's experts, a slice of the vocabulary, the layers of one pipeline
stage.

``counts``     parameters, resident state, FLOPs a sequence and the kernels'
               operations and bytes, from the configuration's sizes alone;
``reference``  the plain float32 reference of this chip's share, its float8
               control, the weights from ``--seed``, and how the files' rows
               become its batch;
``program``    the one place that imports the program's model.

The first two import nothing of the program.
"""
