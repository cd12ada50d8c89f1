"""Operation and byte counts of the work a model and its kernels need."""
