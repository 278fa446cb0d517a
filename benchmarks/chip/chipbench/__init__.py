"""The chip benchmark's own library: traffic, trace reduction, FLOP and
byte counts, the plain references and the comparisons that decide
``correct``. It imports nothing of the program under test except in
``drivers``, which call the program's public entries."""
