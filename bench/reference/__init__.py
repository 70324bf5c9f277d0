"""Plain references: straightforward numpy implementations of what each
cell's timed path computes. They import nothing of the program."""
