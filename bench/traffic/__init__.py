"""Traffic mixes: ``<mix>.json`` parameter files, and the generator modules
they name."""
