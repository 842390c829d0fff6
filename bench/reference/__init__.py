"""Plain references the benchmark holds the program to.  They import
nothing of the program and take nothing it has made."""
