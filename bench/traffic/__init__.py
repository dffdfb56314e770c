"""Traffic generators, one module per mix kind (bench/mixes/*.json "kind")."""
