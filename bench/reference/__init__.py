"""Plain references, one module per architecture family, with the counts
of operations and bytes each family's steps need beside them."""
