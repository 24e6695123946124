"""The plain reference: ZIP-215 Ed25519, canonical vote sign-bytes and
the commit acceptance rules, in straightforward Python.  Imports nothing
of the program under test."""
