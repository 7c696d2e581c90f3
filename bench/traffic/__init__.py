"""Traffic makers, one module each, found by the ``maker`` a workload file
names (``traffic/<maker>.py``).  A maker gives ``cell_data(seed, data,
clients)``, a cell's client shards from its seed, and ``first_batches(shard,
batch, seed, count)``, the batches a client trains on first, in the order
the session draws them."""
