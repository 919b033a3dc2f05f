"""Builders: the only files that know the program's entry points and parameter trees.
A configuration file names its builder; ``build`` hands a generator the object it drives."""
