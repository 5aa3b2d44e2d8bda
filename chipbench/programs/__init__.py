"""The program under test, one module per architecture, named by the
``reference`` key of a configuration file: each builds the program's
model configuration from the file."""
