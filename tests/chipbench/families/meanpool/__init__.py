"""A family that is not the DLRM, for the tests alone: the mean of the
columns' embeddings into one dense layer and a logit. The tests drop it,
with its configuration, into a copy of the benchmark as new files."""
