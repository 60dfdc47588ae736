"""One adapter per game family: the program's game object and its input
encoding, found by the ``adapter`` name in a configuration's file."""
