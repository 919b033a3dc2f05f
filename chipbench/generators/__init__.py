"""Generators: one general load generator per kind of traffic. A traffic file names its
generator and gives it nothing but parameters."""
