"""One driver per kind of traffic: it builds the system under test from a
configuration, warms up the shapes its traffic uses, makes one call of the
closed loop at a time, counts the work, and checks what the calls
returned against the plain reference."""
