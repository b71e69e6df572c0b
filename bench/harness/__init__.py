"""The benchmark's own machinery: traffic, client, reference check,
FLOP and byte counts, trace reduction. Nothing here is the program."""
