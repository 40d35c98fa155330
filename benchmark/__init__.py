"""The benchmark of the served EC path: seal, rebuild and degraded reads
through the daemons, clocked at the client, on the chip. See README.md."""
