SELECT COUNT(*) AS n, SUM(x.v1) AS v1, SUM(big.v2) AS v2, SUM(big.id2) AS id2, SUM(x.id2 * big.id2) AS pair FROM x JOIN big ON x.id3 = big.id3
