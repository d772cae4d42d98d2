"""The chip benchmark's harness: cells and configurations as data, host
spans and captures around the program's layers, the trace reduction, the
plain reference and the comparison that decides ``correct``."""
