"""The benchmark's yardstick: window arithmetic, trace reduction, peaks,
FLOP counts, seeded weights, the result line. Nothing here imports the
program under test (``ddp_tpu``); the drivers do."""
