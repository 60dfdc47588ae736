"""Plain NumPy references, one per game family.  They import nothing of the
program (no ``ggrs_tpu``, no JAX) and take nothing the program has made."""
