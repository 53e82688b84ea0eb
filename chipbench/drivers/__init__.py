"""One module per kind of cell; ``run.py`` finds it by the cell's ``driver``."""
