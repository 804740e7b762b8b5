"""One driver per kind of cell; a configuration names its driver."""
