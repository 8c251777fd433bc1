"""One module per model family, found by the ``family`` of a configuration
file: the plain float32 reference forward, and the operations and bytes of
one decode step computed from the configuration's shapes."""
