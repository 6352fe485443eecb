"""Plain references, one module per architecture, importing nothing of the
program under test."""
