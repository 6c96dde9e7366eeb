"""One file an array operation: how to call the program's public entry, the
least work the operation needs (from ``counts``), and its plain reference
(from ``refs``).  The ``array_program`` driver finds an operation by the
``op`` name a traffic file gives, so a later PR adds an operation by adding
a file here."""
