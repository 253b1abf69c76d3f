"""Plain references, one per kind of configuration (its ``reference``
key).  They import nothing of the program under test."""
