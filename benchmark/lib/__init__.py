"""The benchmark's yardstick: client, launcher, scrape, statistics,
trace reduction, roofline arithmetic. Nothing here imports JAX except
`trace_reduce` (in a child, on the CPU) and `node_main` (inside node 1,
which owns the chip)."""
