"""One driver per entry point of the program; a configuration names its
driver by module name."""
