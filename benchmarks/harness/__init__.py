"""The benchmark's yardstick: runner, load generator, statistics, trace
reduction, roofline arithmetic.  Nothing here imports the program; the
drivers under ``benchmarks/drivers`` are the only files that do."""
