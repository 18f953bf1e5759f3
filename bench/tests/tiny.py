"""A size of each cell that a CPU test run holds: every width cut, the same
protocol, weights and traffic generator, and limits for this size set by
the rule of ``bench/limits`` from CPU readings (sound runs read at most
loss 4.6e-5, grad 0.0035, sync 0.031, change 0.012; the float8 control at
least 5e-4, 0.018, 0.062, 0.029). Not a benchmark size. The program's
default learning rate: at this width the weights move far enough at it,
and at the benchmark's larger one a leaf of a few hundred entries can get
a single entry of the downlink update or none, which the per-leaf sync
reading cannot tell from a fault."""
TINY = {
    "config": {"num_layers": 1, "d_model": 64, "num_heads": 2,
               "num_kv_heads": 2, "head_dim": 32, "d_ff": 128,
               "vocab_size": 256},
    "traffic": {"batch_per_mu": 2, "seq": 32, "pool_batches": 4,
                "base_lr": 0.25},
    "limits": {"loss_gap": 1.5e-4, "grad_gap": 0.008, "sync_gap": 0.05,
               "change_gap": 0.02},
}
SEED = 3_000_000_001  # wider than 31 bits, as the driver's seeds are
