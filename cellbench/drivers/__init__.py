"""Traffic drivers: one module per way of driving the program; a traffic
mix names its driver in ``traffic/<mix>.json``."""
