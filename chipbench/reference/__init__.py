"""What every architecture's plain float32 reference (``chipbench/arch``)
shares: the parameters drawn from a seed, matrix products at a stated
precision, and the training step.  Written from the published descriptions
and independent of the program under test.  See ``train.py`` for what is
compared."""
