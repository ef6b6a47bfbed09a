"""The family ``dlrm``: embedding tables, pairwise dot interaction, top MLP,
a logit against a soft label.

A family is a directory the harness finds by the ``family`` a configuration's
file names (``harness.load_family``), with three modules kept apart:

``counts``     parameters, resident state, FLOPs a row and each kernel's
               operations and bytes, from the configuration's sizes alone;
``reference``  the plain float32 reference, its control, the weights from
               ``--seed``, and how the files' rows become its batch;
``program``    the one place that imports the program's model: builds the
               state and the compiled step through the program's entry
               points and carries the benchmark's weights in and out.

The first two import nothing of the program.
"""
