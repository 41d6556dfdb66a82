"""Traffic generators: every input of a run is made here from `--seed`."""
