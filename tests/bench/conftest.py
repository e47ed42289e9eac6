"""The CPU rehearsal's small traffic for the ``train_hybrid`` kind: the
``train`` kind's (4 rows of 32 tokens), which the hybrid's tiny
configuration (chunks of 8) divides."""

import bench_testing

bench_testing.TRAFFIC_TINY.setdefault("train_hybrid", bench_testing.TRAFFIC_TINY["train"])
