from repro.data.points import StackedBatch, make_batch, make_vanilla_batch
