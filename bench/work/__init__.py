"""Work (FLOPs and bytes) that a kernel or a step needs, from shapes."""
